"""KV pages (models/kv_cache.py ``KIND_BORROWED``: pages that layers
beside their owner read): page bytes a decode step's attention FETCHES
over page bytes in use by its riders, over the window's ``round``
events that dispatched a decode. Fetched: the layers that read pages
(``decode_shared_kv_reads`` / ``decode_context_tokens``: the owner and
its readers) x the entries ONE layer's attention fetches for the
dispatch's last step: the kernel's visited pages x the page size where
the decode program holds the kernel (``decode_kernel_pages``, each
rider to its own last page), else the block loop's window for every
rider (``decode_riders`` x ``decode_window_tokens``). In use: the
riders' own contexts (``decode_context_tokens``). 8 where eight layers
read each entry once; more by what a page's tail, a block's or a
re-read adds. None on a program whose ``round`` events lack the counter
(no layer reads another's pages)."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    page = run.deployment["page_size"]
    fetched = in_use = 0.0
    for e in run.events:
        d = e[5]
        if not (e[2] == "round" and t0 <= e[1] < t1
                and d.get("decode_steps")
                and d.get("decode_shared_kv_reads")
                and d.get("decode_context_tokens")):
            continue
        readers = d["decode_shared_kv_reads"] / d["decode_context_tokens"]
        a_layer = (d["decode_kernel_pages"] * page
                   if d.get("decode_kernel_pages")
                   else d["decode_riders"] * d["decode_window_tokens"])
        fetched += readers * a_layer
        in_use += d["decode_context_tokens"]
    return fetched / in_use if in_use else None
