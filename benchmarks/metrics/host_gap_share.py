"""Engine: sum of host_gap_s over sum of wall_s of the window's
``round`` events. host_gap_s is host time GATING a dispatch (pre-plan
drain and planning), not device idle time: the device can be busy with
the previous dispatch meanwhile. device_idle_share.serve is the idle
share."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    gap = wall = 0.0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            gap += e[5]["host_gap_s"]
            wall += e[5]["wall_s"]
    return 100.0 * gap / wall if wall > 0 else None
