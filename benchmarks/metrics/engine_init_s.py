"""Engine (serve/engine.py): ``wall_s`` of the ``engine_init`` event,
the engine's construction (pool, slots' state, builders, the rest by
name), summed where a run built several engines. Logs one ``[setup]``
line with the parts. None when training, and on a program whose log
holds no such event."""
from benchmarks import setup_parts
from benchmarks.common import log


def read(run):
    if run.kind != "serve":
        return None
    parts = setup_parts.engine_init(run)
    if parts is None:
        return None
    log("[setup] engine_init: " + " ".join(
        f"{k} {v:.3f}" for k, v in parts.items()))
    return parts["wall_s"]
