"""The one general traffic generator. A mix is a data file under
benchmarks/traffic/; a schedule is a pure function of that file and
``--seconds``, token ids of ``--seed``.

Arrival gaps are seeded exponential draws (a Poisson process) and
lengths seeded lognormal draws, clipped as the file says: short gaps run
together and long prompts fall side by side as the draw has them, which
is what makes an open loop's tails. The draw's seed, ``traffic_seed``,
stands IN THE FILE, so every run of a cell replays the same requests at
the same instants, whatever ``--seed``: a difference between two runs
is then the system's, not the draw's. ``--seed`` makes the weights and
the token ids. (serve_bench.make_trace gave the idea of seeded Poisson
arrivals; it has no length distribution.)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np


def draw_lengths(spec: Dict[str, Any], rng, n: int) -> np.ndarray:
    """``n`` whole lengths drawn from ``spec``: {"dist": "lognormal",
    "median", "sigma", "min", "max"} or {"dist": "fixed", "value"}.
    Clipped to [min, max] where given."""
    dist = spec["dist"]
    if dist == "lognormal":
        vals = np.exp(math.log(spec["median"])
                      + spec["sigma"] * rng.standard_normal(n))
    elif dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    vals = np.clip(np.rint(vals), spec.get("min", 1), spec.get("max"))
    return vals.astype(np.int64)


def _streams(traffic: Dict[str, Any]):
    """One generator each for gaps, prompt and output lengths, so that a
    longer schedule continues a shorter one."""
    seed = int(traffic["traffic_seed"])
    return [np.random.default_rng([seed, k]) for k in (1, 2, 3)]


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # seconds after the window opens (< 0: ramp)
    prompt_len: int
    output_len: int
    measured: bool        # due inside the window


def open_schedule(traffic: Dict[str, Any], seconds: float,
                  rate: float = None) -> List[Request]:
    """Open loop: requests due on a schedule, whatever the system does.
    Arrivals start ``ramp_s`` seconds before the window (due < 0,
    unmeasured), so it opens on a system already in its steady state.
    ``rate`` overrides the file's (the knee sweep)."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_rps"] if rate is None else rate)
    ramp = float(traffic.get("ramp_s", 0.0))
    gaps_rng, prompt_rng, output_rng = _streams(traffic)
    due: List[float] = []
    t = -ramp
    while True:
        t += float(gaps_rng.standard_exponential()) / rate
        if t >= seconds:
            break
        due.append(t)
    n = len(due)
    prompts = draw_lengths(traffic["prompt_len"], prompt_rng, n)
    outputs = draw_lengths(traffic["output_len"], output_rng, n)
    return [Request(i, due[i], int(prompts[i]), int(outputs[i]),
                    due[i] >= 0.0) for i in range(n)]


def closed_population(traffic: Dict[str, Any]) -> List[Request]:
    """Closed loop: the requests the clients take in turn (cycled if a
    run outlasts them). ``due_s`` has no meaning."""
    n = int(traffic["population"])
    _, prompt_rng, output_rng = _streams(traffic)
    prompts = draw_lengths(traffic["prompt_len"], prompt_rng, n)
    outputs = draw_lengths(traffic["output_len"], output_rng, n)
    return [Request(i, 0.0, int(prompts[i]), int(outputs[i]), True)
            for i in range(n)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int, shared_prefix: int = 0) -> List[int]:
    """Request ``index``'s prompt: token ids in [1, vocab - 1) from the
    seed; the first ``shared_prefix`` tokens are the same for every
    request of the run."""
    shared_prefix = min(int(shared_prefix), int(length))
    head = np.random.default_rng([int(seed), 3]).integers(
        1, vocab - 1, size=shared_prefix)
    tail = np.random.default_rng([int(seed), 4, int(index)]).integers(
        1, vocab - 1, size=int(length) - shared_prefix)
    return np.concatenate([head, tail]).astype(np.int64).tolist()


class ZipfBatches:
    """Training batches: [batch, seq + 1] token ids whose frequencies
    follow a Zipf law over the vocabulary (rank r has weight
    1 / (r + 1) ** exponent), ranks mapped to ids by a seeded
    permutation. Batch ``step`` is a pure function of seed and step."""

    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int):
        spec = traffic["tokens"]
        if spec["dist"] != "zipf":
            raise ValueError(f"unknown token distribution {spec!r}")
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        self.seed = int(seed)
        w = 1.0 / np.arange(1, vocab + 1) ** float(spec["exponent"])
        self.cdf = np.cumsum(w / w.sum())
        self.ids = np.random.default_rng([self.seed, 5]).permutation(
            vocab).astype(np.int32)

    def __call__(self, step: int) -> np.ndarray:
        u = np.random.default_rng([self.seed, 6, int(step)]).random(
            (self.batch, self.seq + 1))
        ranks = np.minimum(np.searchsorted(self.cdf, u),
                           len(self.ids) - 1)
        return self.ids[ranks]
