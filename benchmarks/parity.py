"""The comparisons that decide ``correct``, with their tolerances.

Serving: chip_smoke's teacher-forced top-2-margin rule (copied). The
engine's greedy tokens are appended to their prompts and run through the
plain float32 reference; at every generated position the engine's token
must have a reference logit within ``tol`` of the reference's best.
Wherever the reference's top-2 margin exceeds ``tol`` that means "the
same token". Sampled tokens are not compared: with random weights a
near-tie flips on rounding and the rest of the continuation follows it.

tol = 2**-5 x the largest |reference logit|: bf16 carries 8 mantissa
bits, the served model rounds to bf16 after every matmul through 16-32
layers while the reference stays in float32, and the two reduce in
different orders; eight bf16 ulps of the logit scale is what PR 23
measured as sufficient (worst deficit 0.0215 against tol 0.1395 at 22
layers) and is far below what a wrong mask, position or head mapping
produces (deficits of the order of the logit scale itself). At least one
position must be decisive, or the check has shown nothing.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

LOGIT_TOL_FRACTION = 2.0 ** -5

# Training: step 0's loss and global gradient norm against the float32
# reference on the same weights and batch. The step computes in bf16
# (8 mantissa bits) with float32 accumulation and float32 logits: with
# the reference at the program's own LayerNorm eps the loss, a mean over
# 24k tokens, agreed to 6e-6..6.6e-5 relative and the gradient norm, a
# sum of squares of bf16-rounded products, to 1.6e-3..2.1e-3 over nine
# seeds on the chip (PR 24). The reference now computes with the
# PUBLISHED eps (1e-5, from the configuration file) and the program has
# 1e-6: by the reference alone at the real sizes (ten seeds at 2-4 x
# 1024 tokens and one at 24 x 1024, CPU, PR 24) that moves the loss by
# -1.2e-4..+1.1e-4 relative (standard deviation 7e-5, either sign by the
# seed) and the gradient norm by +0.6e-3..+2.0e-3; on the chip the two
# together read 1.9e-4 and 4.0e-4 (seed 3000000001). The loss tolerance
# is therefore the rounding plus about six of those deviations, 5e-4
# (it was 2e-4 while the reference was handed the program's eps), and
# the known deviation is the larger part of what it allows; the
# gradient norm's stays. An eps of 1e-4, an fp8/int8 compute path, a
# missing layer or a wrong shift of the targets moves either by far
# more.
TRAIN_LOSS_RTOL = 5e-4
TRAIN_GNORM_RTOL = 1e-2


def margin_rule(ref_logits: np.ndarray, ids: np.ndarray,
                prompt_len: int) -> Dict[str, float]:
    """ref_logits [B, P+G, V] of ids [B, P+G]; positions P-1..P+G-2
    predict the G generated tokens."""
    P = int(prompt_len)
    G = ids.shape[1] - P
    steps = np.asarray(ref_logits[:, P - 1:P - 1 + G], np.float32)
    if not np.isfinite(steps).all():
        return {"ok": False, "why": "reference logits not finite"}
    top2 = np.sort(steps, axis=-1)[..., -2:]
    best, margin = top2[..., 1], top2[..., 1] - top2[..., 0]
    chosen = np.take_along_axis(
        steps, ids[:, P:P + G, None].astype(np.int64), axis=-1)[..., 0]
    deficit = best - chosen
    scale = float(np.abs(steps).max())
    tol = LOGIT_TOL_FRACTION * scale
    decisive = margin > tol
    return {"ok": bool(decisive.any() and (deficit <= tol).all()),
            "steps": int(deficit.size), "decisive": int(decisive.sum()),
            "same_argmax": int((deficit == 0).sum()),
            "worst_deficit": float(deficit.max()), "tol": tol,
            "max_abs_logit": scale}


def train_rule(loss: float, gnorm: float, ref_loss: float,
               ref_gnorm: float, losses: List[float]) -> Dict[str, float]:
    losses = np.asarray(losses, np.float64)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    gnorm_err = abs(gnorm - ref_gnorm) / abs(ref_gnorm)
    finite = bool(np.isfinite(losses).all())
    # the first and last tenth of the window, so one noisy batch at
    # either end does not decide it
    k = max(1, len(losses) // 10)
    fell = bool(losses[-k:].mean() < losses[:k].mean())
    return {"ok": bool(loss_err <= TRAIN_LOSS_RTOL
                       and gnorm_err <= TRAIN_GNORM_RTOL
                       and finite and fell),
            "loss": loss, "ref_loss": ref_loss, "loss_rel_err": loss_err,
            "grad_norm": gnorm, "ref_grad_norm": ref_gnorm,
            "grad_norm_rel_err": gnorm_err, "finite": finite,
            "fell": fell, "first_loss": float(losses[:k].mean()),
            "last_loss": float(losses[-k:].mean())}
