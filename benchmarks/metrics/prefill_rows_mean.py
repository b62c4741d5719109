"""Engine: mean ``prefill_rows`` (rows of the prefill call that carried
a slot's chunk; the call computes four whatever they hold) over the
window's ``round`` events that dispatched a prefill. Near 4 every row
the program pays for is a prompt; near 1 three of four are padding.
None on a program whose ``round`` events lack the key."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    rows = [e[5]["prefill_rows"] for e in run.events
            if e[2] == "round" and t0 <= e[1] < t1
            and e[5].get("prefill_rows")]
    return sum(rows) / len(rows) if rows else None
