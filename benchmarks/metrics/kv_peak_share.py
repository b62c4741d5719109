"""KV pages (models/kv_cache.py, the allocator): the peak of
kv_bytes_in_use / kv_bytes_total over load_report() sampled once a
second inside the window."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    shares = [s["kv_bytes_in_use"] / s["kv_bytes_total"]
              for s in run.samples if t0 <= s["t"] < t1]
    return 100.0 * max(shares) if shares else None
