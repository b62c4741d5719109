"""The benchmark's family seam and its families, in tier-1: the cases
of benchmarks/tests/test_families.py (the seam), test_mixtral_family.py
(the rehearsal family), test_olmoe_family.py (the OLMoE family: the
program against the plain reference at the toy size, the byte counts
and the four readers against hand counts, the rehearsal cell end to
end) and test_solar_open2_family.py (the Solar-Open2 family: the
configuration against its published copy, a chip's share against the
reference, byte counts, three readers, doc-sat, the rehearsal cell)
and test_axk1_family.py (the A.X-K1 family: the configuration against
its published copy, the seeded weights, YaRN by hand, the near-tie
rule, byte counts, three readers, longdoc-sat, the rehearsal cell) and
test_kimi_linear_family.py (the Kimi-Linear family: the configuration
against its published copy, the program against the reference at a
share, seeded and balanced weights, byte counts by kind of layer, the
four readers on a hand-made joined trace, gen-sat, the rehearsal cell)
and test_mellum2_family.py (the Mellum 2 family: the configuration
against its published copy, the program against the reference and the
reference against its quadratic form, the scored tail, byte counts by
kind of layer, the six readers on a hand-made joined trace, the cell on
longdoc-sat as it stands, the rehearsal cell at --trace 0 and 2) and
test_ouro_family.py (the Ouro family: the configuration whole against
its published copy, the program against the reference and the margin
rule against the reference's controls, byte counts with the weights
once a pass, the two readers on a hand-made joined trace with a nested
loop, the cell on chat-sat as it stands, the rehearsal cell) and
test_olmo_hybrid_family.py (the Olmo-Hybrid family: the configuration
against its published copy, the program against the reference and the
margin rule against the reference's controls, byte counts by kind of
layer, the four new readers and the older ones on a hand-made joined
trace, the cell on sample-sat as it stands, the rehearsal cell) and
test_laguna_family.py (the Laguna family: the configuration against its
published copy, the program against the reference and the margin rule
against the reference's controls, byte counts by layer type, the ring
copies by opcode, the three new readers and the older ones on a
hand-made joined trace, the cell on gen-sat as it stands, the rehearsal
cell) and test_deepseek_v32_family.py (the DeepSeek-V3.2 family: the
configuration against its published copy, the program against the
reference through both pools, the near-tie rule with the groups'
boundary, byte counts, the seven new readers on a hand-made joined
trace, the cell on longdoc-sat as it stands, the rehearsal cell at
--trace 0 and 2), collected here so that the suite the driver runs
guards them.
`python -m pytest benchmarks/tests` still runs them where they live."""
import pytest

_FILES = ("benchmarks.tests.test_families",
          "benchmarks.tests.test_mixtral_family",
          "benchmarks.tests.test_olmoe_family",
          "benchmarks.tests.test_solar_open2_family",
          "benchmarks.tests.test_axk1_family",
          "benchmarks.tests.test_kimi_linear_family",
          "benchmarks.tests.test_mellum2_family",
          "benchmarks.tests.test_ouro_family",
          "benchmarks.tests.test_olmo_hybrid_family",
          "benchmarks.tests.test_laguna_family",
          "benchmarks.tests.test_deepseek_v32_family")
pytest.register_assert_rewrite(*_FILES)

from benchmarks.tests.test_families import *          # noqa: E402,F401,F403
from benchmarks.tests.test_mixtral_family import *    # noqa: E402,F401,F403
from benchmarks.tests.test_olmoe_family import *      # noqa: E402,F401,F403
from benchmarks.tests.test_solar_open2_family import *  # noqa: E402,F401,F403
from benchmarks.tests.test_axk1_family import *       # noqa: E402,F401,F403
from benchmarks.tests.test_kimi_linear_family import *  # noqa: E402,F401,F403
from benchmarks.tests.test_mellum2_family import *    # noqa: E402,F401,F403
from benchmarks.tests.test_ouro_family import *       # noqa: E402,F401,F403
from benchmarks.tests.test_olmo_hybrid_family import *  # noqa: E402,F401,F403
from benchmarks.tests.test_laguna_family import *     # noqa: E402,F401,F403
from benchmarks.tests.test_deepseek_v32_family import *  # noqa: E402,F401,F403

# Recorded without tier-1's low-optimisation XLA flags (tests/conftest.py),
# under which the CPU draws a normal's last bits differently: the digests
# are checked where they were recorded, by `python -m pytest
# benchmarks/tests`.
del test_seeded_weights_are_the_parents_bit_for_bit    # noqa: F821


# ------------------------------------------- PR 36's four dispatch readers

# test_the_cell_and_doc_sat and test_the_cell_and_longdoc_sat pin their
# cells' per-layer lists, and the latter BENCHMARK.json's last three
# entries, as PR 34 left them, in files of the benchmark that a `tracing`
# PR may not edit. PR 36 appended four readers of the joined table
# (benchmarks/trace_dispatch.py) to BENCHMARK.json: here the two cases
# run against the file less those four, and the case below pins the four.
# (`python -m pytest benchmarks/tests` fails the two until a `benchmark`
# PR updates their sets: PERF.md section 7.) PR 39 appended a
# configuration, a cell and four readers of its own, and the cell to
# the lists of twelve older metrics: the two cases run against the file
# less those too, the case of the four dispatch readers pins them FOUR
# BEFORE the file's last four, and benchmarks/tests/
# test_kimi_linear_family.py::test_the_cell_and_gen_sat pins PR 39's.
# PR 42 appended a configuration, a cell and six readers, and the cell
# to the lists of thirteen older metrics: the three older cases run
# against the file less those (its last configuration, its last cell,
# that cell's name in every list, its last six readers), the case of
# the four dispatch readers pins them TEN before the file's end, and
# benchmarks/tests/test_mellum2_family.py::
# test_the_cell_and_longdoc_sat_as_it_stands pins PR 42's.
# PR 46 appended a configuration, a cell and two readers, and the cell
# to the lists of ten older metrics: every older case (PR 42's pin
# among them) runs against the file less those too, the case of the
# four dispatch readers pins them TWELVE before the file's end, and
# benchmarks/tests/test_ouro_family.py::
# test_the_cell_and_chat_sat_as_it_stands pins PR 46's.
# PR 49 appended a configuration, a cell and four readers, and the cell
# to the lists of thirteen older metrics: every older case (PR 46's pin
# among them) runs against the file less those too, the case of the
# four dispatch readers pins them SIXTEEN before the file's end, and
# benchmarks/tests/test_olmo_hybrid_family.py::
# test_the_cell_and_sample_sat_as_it_stands pins PR 49's.
# PR 51 appended four readers of the program's build log (no
# configuration, no cell), three to all ten cells and one to the nine
# serving ones: every older case (PR 49's pin among them) runs against
# the file less those four, the case of the four dispatch readers pins
# them TWENTY before the file's end, and tests/test_build_log.py pins
# PR 51's.
# PR 53 appended a configuration, a cell and three readers, and the cell
# to the lists of twenty-three older metrics: every older case (PR 51's
# pin among them, tests/test_build_log.py's) runs against the file less
# those too, the case of the four dispatch readers pins them
# TWENTY-THREE before the file's end, and benchmarks/tests/
# test_laguna_family.py::test_the_cell_and_gen_sat_as_it_stands pins
# PR 53's.
# PR 56 appended a configuration, a cell and seven readers, and the cell
# to the lists of eighteen older metrics: every older case (PR 53's pin
# among them) runs against the file less those too, the case of the
# four dispatch readers pins them THIRTY before the file's end, and
# benchmarks/tests/test_deepseek_v32_family.py::
# test_the_dsv32_cell_and_longdoc_sat_as_it_stands pins PR 56's.
_DISPATCH = ("dispatch_prefill_call_ms", "dispatch_decode_step_ms",
             "dispatch_prefill_share", "dispatch_prefill_call_ms.open")
_PR39 = ("state_peak_share", "linear_state_roofline.by_kind",
         "latent_attn_roofline.by_kind", "moe_experts_roofline.by_kind")
_PR42 = ("decode_sliding_attn_ms", "decode_full_attn_ms",
         "sliding_attn_roofline", "prefill_sliding_attn_share",
         "prefill_full_attn_share", "sliding_resident_share")
_PR42_CELL, _PR42_CONFIG = "mellum2-d8.longdoc-sat", "mellum2-12b-a2.5b-d8"
_PR46 = ("loop_step_roofline", "loop_attn_share")
_PR46_CELL, _PR46_CONFIG = "ouro-2.6b.chat-sat", "ouro-2.6b"
_PR49 = ("hybrid_step_roofline", "prefill_linear_attn_share",
         "state_kv_bytes_ratio", "kda_step_packed_roofline")
_PR49_CELL, _PR49_CONFIG = "olmo-hybrid-d16.sample-sat", "olmo-hybrid-7b-d16"
_PR51 = ("setup_build_s", "setup_program_trace_s", "setup_cold_builds",
         "engine_init_s")
_PR53 = ("swa_moe_step_roofline", "decode_attn_gate_ms",
         "moe_rows_per_expert_mean")
_PR53_CELL, _PR53_CONFIG = "laguna-xs2-d5.gen-sat", "laguna-xs.2-d5"
_PR56 = ("decode_index_ms", "decode_sparse_attn_ms", "index_roofline",
         "sparse_attn_roofline", "prefill_sparse_attn_share",
         "sparse_read_ratio", "sparse_step_roofline")
_PR56_CELL, _PR56_CONFIG = "dsv32-d5.longdoc-sat", "deepseek-v3.2-d5-ep32"
_SAT = ["mistral7b-d16.chat-sat", "olmoe-d8.chat-sat",
        "solar-open2-d4.doc-sat", "axk1-d5.longdoc-sat",
        "kimi-linear-d8.gen-sat", _PR42_CELL, _PR46_CELL, _PR49_CELL,
        _PR53_CELL, _PR56_CELL]


def _less_a_pr(bench, config, cell, readers):
    """BENCHMARK.json without its LAST configuration and cell (which
    must be these), those readers, and the cell's name in any list."""
    assert bench["configs"][-1]["name"] == config
    assert bench["workloads"][-1]["name"] == cell
    bench["configs"], bench["workloads"] = (bench["configs"][:-1],
                                            bench["workloads"][:-1])
    for section in ("end_to_end", "per_layer"):
        bench[section] = [
            dict(m, workloads=[w for w in m["workloads"] if w != cell])
            if "workloads" in m else m
            for m in bench[section] if m["name"] not in readers]
    return bench


def _less_pr56(bench):
    """BENCHMARK.json as PR 55 left it."""
    return _less_a_pr(bench, _PR56_CONFIG, _PR56_CELL, _PR56)


def _less_pr53(bench):
    """BENCHMARK.json as PR 52 left it."""
    return _less_a_pr(_less_pr56(bench), _PR53_CONFIG, _PR53_CELL, _PR53)


def _less_pr51(bench):
    """BENCHMARK.json as PR 50 left it: without PR 53's entries and
    then its last four readers, which must be these."""
    bench = _less_pr53(bench)
    assert tuple(m["name"] for m in bench["per_layer"][-4:]) == _PR51
    bench["per_layer"] = bench["per_layer"][:-4]
    return bench


def _less_pr49(bench):
    """BENCHMARK.json as PR 48 left it."""
    return _less_a_pr(_less_pr51(bench), _PR49_CONFIG, _PR49_CELL, _PR49)


def _less_pr46(bench):
    """BENCHMARK.json as PR 45 left it."""
    return _less_a_pr(_less_pr49(bench), _PR46_CONFIG, _PR46_CELL, _PR46)


def _less_pr42(bench):
    """BENCHMARK.json as PR 40 left it."""
    return _less_a_pr(_less_pr46(bench), _PR42_CONFIG, _PR42_CELL, _PR42)


def _less_the_dispatch_readers(case, also=_DISPATCH + _PR39):
    def test(monkeypatch):
        from benchmarks import common
        bench = _less_pr42(common.load_benchmark())
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in also]
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__ = case.__name__
    test.__doc__ = case.__doc__
    return test


test_the_cell_and_doc_sat = _less_the_dispatch_readers(
    test_the_cell_and_doc_sat)                          # noqa: F821
test_the_cell_and_longdoc_sat = _less_the_dispatch_readers(
    test_the_cell_and_longdoc_sat)                      # noqa: F821
test_the_cell_and_gen_sat = _less_the_dispatch_readers(
    test_the_cell_and_gen_sat, also=())                 # noqa: F821


def _as_pr45_left_it(case):
    def test(monkeypatch):
        from benchmarks import common
        bench = _less_pr46(common.load_benchmark())
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__ = case.__name__
    test.__doc__ = case.__doc__
    return test


test_the_cell_and_longdoc_sat_as_it_stands = _as_pr45_left_it(
    test_the_cell_and_longdoc_sat_as_it_stands)         # noqa: F821


def _as_pr48_left_it(case):
    def test(monkeypatch):
        from benchmarks import common
        bench = _less_pr49(common.load_benchmark())
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__ = case.__name__
    test.__doc__ = case.__doc__
    return test


test_the_cell_and_chat_sat_as_it_stands = _as_pr48_left_it(
    test_the_cell_and_chat_sat_as_it_stands)            # noqa: F821


def _as_pr50_left_it(case):
    def test(monkeypatch):
        from benchmarks import common
        bench = _less_pr51(common.load_benchmark())
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__ = case.__name__
    test.__doc__ = case.__doc__
    return test


test_the_cell_and_sample_sat_as_it_stands = _as_pr50_left_it(
    test_the_cell_and_sample_sat_as_it_stands)          # noqa: F821


def _as_pr55_left_it(case):
    def test(monkeypatch):
        from benchmarks import common
        bench = _less_pr56(common.load_benchmark())
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__ = case.__name__
    test.__doc__ = case.__doc__
    return test


test_the_cell_and_gen_sat_as_it_stands = _as_pr55_left_it(
    test_the_cell_and_gen_sat_as_it_stands)             # noqa: F821


@pytest.mark.parametrize("name", _DISPATCH)
def test_dispatch_readers_are_appended_to_the_benchmark(name):
    from benchmarks import common
    bench = common.load_benchmark()
    assert tuple(m["name"] for m in bench["per_layer"][-34:]) == \
        _DISPATCH + _PR39 + _PR42 + _PR46 + _PR49 + _PR51 + _PR53 + _PR56
    m = common.find_named(bench["per_layer"], name, "metric")
    want = {"name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "serve_tokens_per_s", "workloads": _SAT}
    if name == "dispatch_prefill_share":
        want["unit"] = "%"
    if name.endswith(".open"):
        want.update(moves="itl_p50_ms",
                    workloads=["mistral7b-d16.chat-r80"])
    assert m == want
    # every listed cell reports the end-to-end metric it moves, and the
    # reader loads by its name
    moved = common.find_named(bench["end_to_end"], m["moves"], "metric")
    assert set(m["workloads"]) <= set(moved["workloads"])
    assert callable(common.load_metric_reader(name))
