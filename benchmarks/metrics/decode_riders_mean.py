"""Engine: mean ``decode_riders`` (slots in the decode dispatch) over the
window's ``round`` events that dispatched a decode. With the prefill
budget it explains serve_tokens_per_s: tokens a round = riders x steps.
None on a program whose ``round`` events lack the key."""


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    riders = [e[5]["decode_riders"] for e in run.events
              if e[2] == "round" and t0 <= e[1] < t1
              and e[5].get("decode_steps")]
    return sum(riders) / len(riders) if riders else None
