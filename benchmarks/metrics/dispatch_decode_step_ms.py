"""Model step (the decode program): device time of the ``jit_decode``
executions that benchmarks/trace_dispatch.py matched to their rounds,
over the ``decode_steps`` those rounds dispatched: the engine's own
count of the steps, not one inferred from how often an operation ran.
None where the join gives nothing."""
from benchmarks import trace_dispatch


def read(run):
    rows = trace_dispatch.table(run)
    return None if rows is None else trace_dispatch.decode_step_ms(rows)
