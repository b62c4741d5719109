"""A schedule is a pure function of the traffic file and the seconds:
the draw's seed stands in the file, so every --seed replays the same
requests; token ids come from --seed. Arrivals are a Poisson process,
lengths clipped lognormal draws with the declared medians."""
import numpy as np
import pytest

from benchmarks import common, trafficgen


@pytest.fixture(scope="module")
def chat():
    return common.load_json("traffic", "chat-r80.json")


def test_one_schedule_whatever_the_seed(chat):
    a = trafficgen.open_schedule(chat, 40)
    assert a == trafficgen.open_schedule(chat, 40)
    assert a != trafficgen.open_schedule(
        dict(chat, traffic_seed=chat["traffic_seed"] + 1), 40)
    assert trafficgen.prompt_tokens(7, 3, 50, 32768) == \
        trafficgen.prompt_tokens(7, 3, 50, 32768)
    assert trafficgen.prompt_tokens(7, 3, 50, 32768) != \
        trafficgen.prompt_tokens(8, 3, 50, 32768)


def test_a_longer_window_continues_a_shorter_one(chat):
    short = trafficgen.open_schedule(chat, 10)
    long = trafficgen.open_schedule(chat, 40)
    assert [(r.due_s, r.prompt_len, r.output_len) for r in short] == \
        [(r.due_s, r.prompt_len, r.output_len) for r in long[:len(short)]]


def test_the_committed_draw_offers_what_the_file_declares(chat):
    """PERF.md section 4: requests, prompt tokens and output tokens over
    ramp + window within 10 % of rate x the distribution's mean."""
    reqs = trafficgen.open_schedule(chat, 40)
    span = chat["ramp_s"] + 40
    assert len(reqs) == 158 and sum(r.measured for r in reqs) == 122
    big = np.random.default_rng(0)
    for key in ("prompt_len", "output_len"):
        mean = trafficgen.draw_lengths(chat[key], big, 1_000_000).mean()
        got = sum(getattr(r, key) for r in reqs) / span
        assert abs(got / (chat["rate_rps"] * mean) - 1) < 0.10, key
    assert abs(len(reqs) / (chat["rate_rps"] * span) - 1) < 0.10


def test_lengths_clipped_and_centred(chat):
    rng = np.random.default_rng(1)
    p = trafficgen.draw_lengths(chat["prompt_len"], rng, 200_001)
    o = trafficgen.draw_lengths(chat["output_len"], rng, 200_001)
    assert p.min() == 16 and p.max() == 2048
    assert o.min() == 8 and o.max() == 512
    assert abs(np.median(p) - 256) <= 3 and abs(np.median(o) - 96) <= 1
    # sigma as declared: the 84th percentile is median * e**sigma
    assert abs(np.percentile(p, 84.13) / 256 - np.exp(0.9)) < 0.05
    assert abs(np.percentile(o, 84.13) / 96 - np.exp(0.7)) < 0.05
    fixed = trafficgen.draw_lengths({"dist": "fixed", "value": 96}, rng, 5)
    assert fixed.tolist() == [96] * 5
    with pytest.raises(ValueError):
        trafficgen.draw_lengths({"dist": "uniform"}, rng, 5)


def test_arrivals_are_a_poisson_process_at_the_declared_rate(chat):
    reqs = trafficgen.open_schedule(dict(chat, ramp_s=0.0), 4000, rate=3.0)
    due = np.array([r.due_s for r in reqs])
    assert all(r.measured for r in reqs)
    assert (np.diff(due) > 0).all() and 0.0 <= due[0] and due[-1] < 4000
    gaps = np.diff(due)
    assert abs(len(reqs) / 12000 - 1) < 0.03
    # exponential gaps: mean 1 / rate, median ln 2 / rate, and as many
    # short gaps running together as independence gives
    assert abs(gaps.mean() * 3.0 - 1) < 0.03
    assert abs(np.median(gaps) * 3.0 - np.log(2)) < 0.03
    short = gaps < np.log(2) / 3.0
    assert abs((short[1:] & short[:-1]).mean() - 0.25) < 0.02
    # counts in 10 s bins: variance equals mean
    counts = np.histogram(due, bins=400, range=(0, 4000))[0]
    assert abs(counts.var() / counts.mean() - 1) < 0.2
    ramp = trafficgen.open_schedule(chat, 40)
    assert [r.measured for r in ramp] == [r.due_s >= 0 for r in ramp]
    assert ramp[0].due_s >= -chat["ramp_s"] and not ramp[0].measured
    with pytest.raises(ValueError):
        trafficgen.open_schedule(dict(chat, arrivals="uniform"), 40)


def test_closed_population_is_the_files():
    sat = common.load_json("traffic", "chat-sat.json")
    a = trafficgen.closed_population(sat)
    assert a == trafficgen.closed_population(sat)
    assert len(a) == sat["population"]
    # the committed mix fixes both lengths (PERF.md, PR 24)
    assert {(r.prompt_len, r.output_len) for r in a} == {(256, 96)}
    # a drawn mix: one draw per traffic_seed, long prompts side by side
    # as drawn (no stratification)
    chat = common.load_json("traffic", "chat-r80.json")
    drawn = dict(sat, prompt_len=chat["prompt_len"])
    b = trafficgen.closed_population(drawn)
    assert b == trafficgen.closed_population(drawn)
    assert b != trafficgen.closed_population(dict(drawn, traffic_seed=3))
    p = np.array([r.prompt_len for r in b])
    assert len(set(p[:8])) > 1 and p.max() == 2048 and p.min() >= 16


def test_prompts_in_vocabulary_and_shared_prefix():
    a = trafficgen.prompt_tokens(9, 0, 100, 32768, shared_prefix=40)
    b = trafficgen.prompt_tokens(9, 1, 100, 32768, shared_prefix=40)
    assert a[:40] == b[:40] and a[40:] != b[40:]
    assert min(a) >= 1 and max(a) < 32767 and len(a) == 100


def test_zipf_batches():
    tr = common.load_json("traffic", "train-b24.json")
    z = trafficgen.ZipfBatches(tr, 2**31 + 5, 50257)
    b0, b1 = z(0), z(1)
    assert b0.shape == (24, 1025) and b0.dtype == np.int32
    assert (z(0) == b0).all() and (b0 != b1).any()
    assert b0.min() >= 0 and b0.max() < 50257
    # Zipf: the commonest token is far commoner than 1 / vocab
    _, counts = np.unique(b0, return_counts=True)
    assert counts.max() / b0.size > 0.05
