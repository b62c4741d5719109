"""Engine (serve/engine.py): median over the requests submitted inside
the window of first ``admit`` -> ``first_token``, from the engine's event
log: the time a request spends INSIDE a slot waiting for the 256-token
prefill budget a round and being prefilled. queue_wait_p95_ms cannot see
it (admission is immediate while slots are free); with the median queue
wait and entry_overhead_ms it is what ttft_median_ms is made of (medians do
not add exactly; PERF.md section 6)."""
import statistics

from benchmarks.common import log


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    submit, admit, first = {}, {}, {}
    for e in run.events:
        etype, rid = e[2], e[3]
        if etype == "submit" and t0 <= e[1] < t1:
            submit[rid] = e[1]
        elif etype == "admit" and rid not in admit:
            admit[rid] = e[1]
        elif etype == "first_token" and rid not in first:
            first[rid] = e[1]
    done = [r for r in submit if r in admit and r in first]
    if not done:
        return None
    waits = [first[r] - admit[r] for r in done]
    log(f"[ttft parts] over {len(done)} requests of the window: median "
        f"submit->admit "
        f"{1e3 * statistics.median(admit[r] - submit[r] for r in done):.1f}"
        f" ms, median admit->first token "
        f"{1e3 * statistics.median(waits):.1f} ms")
    return 1e3 * statistics.median(waits)
