"""Engine: prompt tokens granted over the planner's prefill budget,
summed over the window's ``round`` events (``prefill_tokens`` /
``prefill_budget``). Near 100 % the engine is bound by the budget and
requests wait inside slots for it (prefill_in_slot_p50_ms). None on a
program whose ``round`` events lack the keys."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    granted = budget = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1 and "prefill_budget" in e[5]:
            granted += e[5]["prefill_tokens"]
            budget += e[5]["prefill_budget"]
    return 100.0 * granted / budget if budget else None
