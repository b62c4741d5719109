"""Engine (a model that decodes by blocks): percent of the window's
slot-forwards that a slot rode AFTER its last commit: the host plans
forwards on a BOUND (blocks left x (denoising steps + 1)) and a slot
that finished its blocks earlier idles on the device, writing nothing,
until the readback shows it (the ``round`` events'
``denoise_idle_forwards`` over those and ``denoise_rider_forwards``;
the family's ``denoise_counters``). 0 where the bound is exact (the
schedules that reveal a fixed count a step, and the dynamic one where
no confidence clears its threshold). None on a program whose events
lack the counters, or for a family without the reading."""


def read(run):
    counters = getattr(getattr(run, "family", None), "denoise_counters",
                       None)
    got = counters(run) if run.kind == "serve" and counters else None
    if not got:
        return None
    return 100.0 * got["idle_forwards"] / (
        got["idle_forwards"] + got["rider_forwards"])
