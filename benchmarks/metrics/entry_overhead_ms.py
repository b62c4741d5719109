"""Entry layer (serve/api.py, serve/llm.py): median over finished
requests of the client's wall time (send to last token) less the
engine's own submit -> retire span of the same request, matched by the
trace id the client sent. Host clock both sides (time.monotonic)."""
import statistics


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    rid_of, submit, retire = {}, {}, {}
    for e in run.events:
        etype, rid = e[2], e[3]
        if etype == "submit" and isinstance(e[5], dict):
            rid_of[e[5].get("trace_id")] = rid
            submit[rid] = e[1]
        elif etype == "retire":
            retire[rid] = e[1]
    over = []
    for c in run.measured:
        rid = rid_of.get(c.trace_id)
        if (c.error is None and not c.abandoned and c.done is not None
                and rid in retire):
            over.append((c.done - c.sent) - (retire[rid] - submit[rid]))
    return 1e3 * statistics.median(over) if over else None
