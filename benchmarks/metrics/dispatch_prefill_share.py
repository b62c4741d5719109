"""Model step: percent of the matched device time of ``jit_prefill``
and ``jit_decode`` that the prefill calls took (the chip's time by
program, as benchmarks/trace_dispatch.py joins executions to rounds).
None where the join gives nothing."""
from benchmarks import trace_dispatch


def read(run):
    rows = trace_dispatch.table(run)
    return None if rows is None else trace_dispatch.prefill_share(rows)
