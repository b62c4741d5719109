"""BENCHMARK.json as an earlier PR left it.

Cases of ``benchmarks/tests/`` pin the file "as PR n left it", in files
a later PR may not edit, and every ``model_config`` or ``tracing`` PR
since has APPENDED to it: a configuration and a cell (and the cell's
name to older metrics' lists) and per-layer readers, always at the end.
``APPENDED`` is that history, one row a PR, and ``as_of(pr)`` peels the
file back through it from the end, so a pinned case runs against the
file it was written for (``pinned``). The next PR that appends adds one
row; nothing here counts from the end of a list by hand.
"""
from typing import NamedTuple, Optional, Tuple


class Appended(NamedTuple):
    pr: int
    config: Optional[str]       # the configuration it appended, if any
    cell: Optional[str]         # the cell, if any
    readers: Tuple[str, ...]    # the per-layer readers, in the file's order


APPENDED = (
    Appended(36, None, None, (
        "dispatch_prefill_call_ms", "dispatch_decode_step_ms",
        "dispatch_prefill_share", "dispatch_prefill_call_ms.open")),
    Appended(39, "kimi-linear-48b-a3b-d8-ep4", "kimi-linear-d8.gen-sat", (
        "state_peak_share", "linear_state_roofline.by_kind",
        "latent_attn_roofline.by_kind", "moe_experts_roofline.by_kind")),
    Appended(42, "mellum2-12b-a2.5b-d8", "mellum2-d8.longdoc-sat", (
        "decode_sliding_attn_ms", "decode_full_attn_ms",
        "sliding_attn_roofline", "prefill_sliding_attn_share",
        "prefill_full_attn_share", "sliding_resident_share")),
    Appended(46, "ouro-2.6b", "ouro-2.6b.chat-sat", (
        "loop_step_roofline", "loop_attn_share")),
    Appended(49, "olmo-hybrid-7b-d16", "olmo-hybrid-d16.sample-sat", (
        "hybrid_step_roofline", "prefill_linear_attn_share",
        "state_kv_bytes_ratio", "kda_step_packed_roofline")),
    Appended(51, None, None, (
        "setup_build_s", "setup_program_trace_s", "setup_cold_builds",
        "engine_init_s")),
    Appended(53, "laguna-xs.2-d5", "laguna-xs2-d5.gen-sat", (
        "swa_moe_step_roofline", "decode_attn_gate_ms",
        "moe_rows_per_expert_mean")),
    Appended(56, "deepseek-v3.2-d5-ep32", "dsv32-d5.longdoc-sat", (
        "decode_index_ms", "decode_sparse_attn_ms", "index_roofline",
        "sparse_attn_roofline", "prefill_sparse_attn_share",
        "sparse_read_ratio", "sparse_step_roofline")),
    Appended(60, "phi-4-mini-flash-reasoning", "phi4-mini-flash.reason-sat", (
        "decode_ssm_ms", "prefill_ssm_share", "ssm_scan_roofline",
        "ssm_prefill_scan_roofline", "decode_shared_attn_ms",
        "shared_attn_roofline", "shared_kv_read_ratio")),
    Appended(63, "sdar-30b-a3b-chat-d6", "sdar-30b-d6.gen-sat", (
        "denoise_tokens_per_forward", "denoise_commit_share",
        "denoise_idle_share", "denoise_attn_ms", "denoise_step_roofline")),
    Appended(65, "granite-4.0-h-small-d10-ep2",
             "granite4-h-small-d10.gen-sat", (
                 "ssd_prefill_roofline", "ssm_moe_step_roofline")),
)


def row(pr: int) -> Appended:
    return next(r for r in APPENDED if r.pr == pr)


def undo(bench: dict, r: Appended) -> dict:
    """``bench`` without what row ``r`` appended, which must be its
    tail: the last configuration, the last cell (and its name in every
    metric's list) and the last per-layer readers."""
    bench = dict(bench)
    if r.config is not None:
        assert bench["configs"][-1]["name"] == r.config, r
        bench["configs"] = bench["configs"][:-1]
    n = len(r.readers)
    assert tuple(m["name"] for m in bench["per_layer"][-n:]) == r.readers, r
    bench["per_layer"] = bench["per_layer"][:-n]
    if r.cell is not None:
        assert bench["workloads"][-1]["name"] == r.cell, r
        bench["workloads"] = bench["workloads"][:-1]
        for section in ("end_to_end", "per_layer"):
            bench[section] = [
                dict(m, workloads=[w for w in m["workloads"] if w != r.cell])
                if "workloads" in m else m for m in bench[section]]
    return bench


def as_of(pr: int, bench: Optional[dict] = None) -> dict:
    """BENCHMARK.json (or ``bench``) as PR ``pr`` left it: every later
    row undone, the latest first."""
    if bench is None:
        from benchmarks import common
        bench = common.load_benchmark()
    for r in reversed(APPENDED):
        if r.pr > pr:
            bench = undo(bench, r)
    return bench


def pinned(case, pr: int):
    """``case`` of ``benchmarks/tests/``, run against the file as PR
    ``pr`` left it."""
    def test(monkeypatch):
        from benchmarks import common
        bench = as_of(pr)
        monkeypatch.setattr(common, "load_benchmark", lambda: bench)
        case()
    test.__name__, test.__doc__ = case.__name__, case.__doc__
    return test
