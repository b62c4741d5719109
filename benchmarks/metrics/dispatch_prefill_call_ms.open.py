"""Model step (the chunked-prefill program) under an open loop: median
device time of one ``jit_prefill`` execution at the widest
``prefill_width`` the traced rounds dispatched (a last chunk is short,
and the call's ``T`` is bucketed to powers of two: the ``round`` event
says which), over the executions benchmarks/trace_dispatch.py matched
to their rounds. None where the join gives nothing."""
from benchmarks import trace_dispatch


def read(run):
    rows = trace_dispatch.table(run)
    return (None if rows is None
            else trace_dispatch.prefill_call_ms(rows, widest=True))
