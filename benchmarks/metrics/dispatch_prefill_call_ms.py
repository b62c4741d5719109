"""Model step (the chunked-prefill program): median device time of one
``jit_prefill`` execution in the trace, over the executions that
benchmarks/trace_dispatch.py matched to the rounds that dispatched them
(by order from the engine's ``trace_start``, checked by its counts and
the trace's clock). Needs the trace itself (``run.trace_dir``,
--trace 2); None where the join gives nothing: a program whose
``trace_start`` carries no counts, or a join that is refused."""
from benchmarks import trace_dispatch


def read(run):
    rows = trace_dispatch.table(run)
    return None if rows is None else trace_dispatch.prefill_call_ms(rows)
