"""Model step (a model that decodes by blocks): percent of the window's
rider-forwards that were COMMITS: the forward of a finished block that
writes its K/V and reveals nothing (the ``round`` events'
``denoise_commits`` over ``denoise_rider_forwards``; the family's
``denoise_counters``). 1 in T + 1 where every block runs its T steps
(20 % at T = 4): what a commit fused into the next block's first
forward would take out. None on a program whose events lack the
counters, or for a family without the reading."""


def read(run):
    counters = getattr(getattr(run, "family", None), "denoise_counters",
                       None)
    got = counters(run) if run.kind == "serve" and counters else None
    return 100.0 * got["commits"] / got["rider_forwards"] if got else None
