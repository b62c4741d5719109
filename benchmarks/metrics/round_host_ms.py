"""Engine: mean over the window's rounds of ``admit_s + plan_s +
dispatch_s`` of the ``round`` event: host work that waits for no device.
``readback_s`` (the trailing drain, which blocks on the device) is logged
beside it by the engine and not added. None on a program whose ``round``
events lack the keys."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    host = [e[5]["admit_s"] + e[5]["plan_s"] + e[5]["dispatch_s"]
            for e in run.events
            if e[2] == "round" and t0 <= e[1] < t1 and "admit_s" in e[5]]
    return 1e3 * sum(host) / len(host) if host else None
