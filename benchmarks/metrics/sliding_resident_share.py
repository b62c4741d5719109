"""KV pages (models/kv_cache.py ``SlidingRing``, the engine's slots):
what the sliding-window layers HOLD for the requests in flight, over
what the same layers would hold for the same contexts did nothing age:
the mean over the window's load_report() samples of (slots that hold a
request x the family's ``sliding_bytes_per_slot``: the rings, whatever
the contexts) over (``kv_bytes_in_use`` x sliding layers / full layers:
the pages the full layers really hold for those contexts, a layer,
which is what an un-aged sliding layer would hold). 100 % = nothing
ages; the window, a chunk and the slack over the mean context here.
Lower is better. The harness's sampler keeps ``free_slots`` and
``kv_bytes_in_use``; the slot's constant is the family's count, which a
test holds to the engine's ``load_report()["sliding_bytes_per_slot"]``.
None for a family that counts no sliding layer, or before a page is in
use."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "sliding_bytes_per_slot"):
        return None
    slots = run.deployment["max_slots"]
    per_slot = fam.sliding_bytes_per_slot(run.cfg)
    t0, t1 = run.window
    held = unaged = 0.0
    for s in run.samples:
        if t0 <= s["t"] < t1:
            held += (slots - s["free_slots"]) * per_slot
            unaged += fam.unaged_bytes(run.cfg, s["kv_bytes_in_use"])
    return 100.0 * held / unaged if unaged else None
