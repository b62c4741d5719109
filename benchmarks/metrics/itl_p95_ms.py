"""Gaps between tokens, the tail: 95th percentile over the window's
requests of (last - first token time) / (tokens - 1) at the client. With
122 requests a window it lies between the sixth and the seventh slowest:
the requests that decoded through the window's worst burst. It carries
no bound: itl_p50_ms is the end-to-end metric, and this stands beside it
so that a change to the tail shows (PERF.md section 2)."""


def read(run):
    # the runner takes it from the clients' clocks beside the medians
    return getattr(run, "e2e", {}).get("itl_p95_ms")
