"""Model step (models/llama.py, the engine's decode program): device
time of the decode executable's runs in the trace over the decode steps
they took (the program loops; trace_reduce.loop_steps counts its body's
operations)."""
from benchmarks import trace_reduce


def read(run):
    s = trace_reduce.loop_step_seconds(run.trace, "jit_decode")
    return None if s is None else 1e3 * s
