"""Train step: share of jit_step_fn's device self time under the
``loss_head`` scope of models/gpt2.linear_cross_entropy, forward and
backward (``jvp(loss_head)``, ``transpose(jvp(loss_head))``): the
projection to 50257 classes and its fp32 cross-entropy. Needs the trace
itself (``run.trace_dir``, --trace 2)."""
from benchmarks import trace_parts


def read(run):
    if run.kind != "train":
        return None
    got = trace_parts.for_run(run, "jit_step_fn")
    if not got:
        return None
    total = sum(got["parts"].values())
    return 100.0 * got["parts"].get("loss_head", 0.0) / total if total \
        else None
