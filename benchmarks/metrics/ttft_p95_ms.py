"""Entry to first token, the tail: 95th percentile over the window's
requests of the time from when a request was DUE to its first streamed
token (host clock at the client). With 122 requests a window it lies
between the sixth and the seventh slowest, the end of the window's
worst burst, and one request that crosses a round of the engine moves
it, so it carries no bound; it stands beside ttft_median_ms so that a
change to the tail shows (PERF.md section 2)."""


def read(run):
    # the runner takes it from the clients' clocks beside the medians
    return getattr(run, "e2e", {}).get("ttft_p95_ms")
