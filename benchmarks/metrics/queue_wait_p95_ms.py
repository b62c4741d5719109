"""Engine (serve/engine.py, serve/scheduler.py): 95th percentile of
submit -> first admit over the requests submitted inside the window,
from the engine's event log."""
import numpy as np


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    submit, admit = {}, {}
    for e in run.events:
        etype, rid = e[2], e[3]
        if etype == "submit" and t0 <= e[1] < t1:
            submit[rid] = e[1]
        elif etype == "admit" and rid not in admit:
            admit[rid] = e[1]
    waits = [admit[r] - t for r, t in submit.items() if r in admit]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
