"""Model step: of the (token, expert) pairs the router made of the live
tokens, the share that landed on experts THIS chip holds: the ``round``
events' moe_pairs over their moe_pairs_routed, over the window. A
mixture that holds all its experts reads 100; one chip's share of an
expert-parallel group reads experts held / router width under even
routing (40 / 320 = 12.5 %), which guards the router's width, the
choice rule and the share the configuration states: a router cut to the
held experts would read 100, a biased one something else. None on a
program whose ``round`` events lack moe_pairs_routed (one that knows no
share, or a dense model)."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    held = routed = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            held += e[5].get("moe_pairs", 0)
            routed += e[5].get("moe_pairs_routed", 0)
    return 100.0 * held / routed if routed else None
