"""KV pages (models/kv_cache.py ``RecurrentState``, the engine's slots):
the peak of state_bytes_in_use / state_bytes_total of load_report()
inside the window: how full the OTHER kind of request state is, the one
a slot holds whatever its context's length, where kv_peak_share reads
the pages. load_report() gives state_bytes_in_use = the bytes a slot's
recurrent layers keep x the slots that hold a request and
state_bytes_total = the same x all slots; the harness's sampler keeps
``free_slots`` of each report and not those two keys, so the share is
taken as (slots - free_slots) / slots, which is their ratio by
load_report's own arithmetic (``slots`` is the deployment's
``max_slots``). None for a family that counts no state a slot (a model
with pages only reads 0 / 0)."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "state_bytes"):
        return None
    slots = run.deployment["max_slots"]
    t0, t1 = run.window
    shares = [(slots - s["free_slots"]) / slots
              for s in run.samples if t0 <= s["t"] < t1]
    return 100.0 * max(shares) if shares else None
