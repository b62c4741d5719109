"""Model step: distinct experts a layer's router chose in one forward
pass (a decode step of the riders, or a prefill call's live tokens),
averaged over the window: the ``round`` events' moe_experts_touched
over their moe_layer_steps. It says how much of the experts' weights a
step must stream: all 64 would make the mixture a dense layer of 64
experts' bytes. None on a program whose ``round`` events lack the keys
(a dense model). Logs the window's ``[moe]`` line: the pairs the router
counted over the live tokens the host dispatched (riders x steps +
prefill tokens), which a dropless mixture makes experts a token x
layers (a round's counters reach the NEXT round's event, so the two
sums agree where the window's first and last rounds are alike)."""
from benchmarks.common import log


def read(run):
    if run.kind != "serve":
        return None
    t0, t1 = run.window
    touched = layer_steps = pairs = tokens = 0
    for e in run.events:
        if e[2] == "round" and t0 <= e[1] < t1:
            touched += e[5].get("moe_experts_touched", 0)
            layer_steps += e[5].get("moe_layer_steps", 0)
            pairs += e[5].get("moe_pairs", 0)
            tokens += (e[5].get("decode_riders", 0)
                       * e[5].get("decode_steps", 0)
                       + e[5].get("prefill_tokens", 0))
    if not layer_steps:
        return None
    log(f"[moe] window: moe_pairs {pairs} over {tokens} live tokens "
        f"dispatched = {pairs / max(1, tokens):.4f} a token; "
        f"experts a token x layers = "
        f"{run.cfg['num_experts_per_tok'] * run.cfg['num_hidden_layers']}"
        f"; experts touched {touched} over {layer_steps} layer-steps")
    return touched / layer_steps
