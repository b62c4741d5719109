"""KV pages (models/kv_cache.py: ``RecurrentState`` beside the page
pool): the bytes of recurrent state the live slots hold over the bytes
of K/V pages in use, the median over load_report() sampled once a
second inside the window: which kind of request state the deployment
is made of (above 1, a request's fixed-size state outweighs the K/V its
context has grown; the ratio falls as contexts grow). The sampler keeps
``free_slots`` and ``kv_bytes_in_use`` of each report and not the
state's two keys, so the state's bytes are taken as (slots -
free_slots) x what the family counts a slot (``state_bytes_per_slot``:
its ``state_bytes`` and ``conv_tail_bytes`` a linear layer x
``n_kda_layers``), which is load_report's own arithmetic (``slots`` is
the deployment's ``max_slots``). None for a family that counts no such state, and where
no sample holds a page in use."""
import statistics


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "state_bytes_per_slot"):
        return None
    per_slot = fam.state_bytes_per_slot(run.cfg)
    slots = run.deployment["max_slots"]
    t0, t1 = run.window
    ratios = [(slots - s["free_slots"]) * per_slot / s["kv_bytes_in_use"]
              for s in run.samples
              if t0 <= s["t"] < t1 and s["kv_bytes_in_use"]]
    return statistics.median(ratios) if ratios else None
