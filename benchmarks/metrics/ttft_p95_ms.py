"""Entry to first token, the tail: 95th percentile over the window's
requests of the time from when a request was DUE to its first streamed
token (host clock at the client). With 35 requests a window it lies
between the second and the third slowest, and one request that crosses
a round of the engine moves it by up to 0.6 s, so it carries no bound:
ttft_p50_ms is the end-to-end metric, and this stands beside it so that
a change to the tail shows (PERF.md section 2)."""


def read(run):
    # the runner takes it from the clients' clocks beside the medians
    return getattr(run, "e2e", {}).get("ttft_p95_ms")
