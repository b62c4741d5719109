"""Entry to first token, the median: over the window's requests, the
time from when a request was DUE to its first streamed token (host
clock at the client). It was the end-to-end metric ttft_p50_ms until
PR 27's check: an open loop's arrivals are fixed instants and the
engine's rounds run free, so a drift of milliseconds decides which
round admits a request, the requests behind it shift with it, and the
same code on the same draw reads one of several trajectories 2-5 %
apart, at 0.9, 2.1 and 2.8 req/s alike (PERF.md section 6). No bound
holds on a quiet and on a busy machine both, so it stands per layer,
unbounded, beside its tail; itl_p50_ms carries the cell's bound."""


def read(run):
    # the runner takes it from the clients' clocks beside the tails
    return getattr(run, "e2e", {}).get("ttft_p50_ms")
