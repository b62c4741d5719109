"""Model step (a model that decodes by blocks): tokens emitted a
rider-forward over the window: the ``round`` events'
``denoise_emitted`` over their ``denoise_rider_forwards`` (the block
program's own counters, serve/step_programs.py ``BLOCK_COUNTERS``; the
family's ``denoise_counters`` sums them). A block of L tokens costs its
denoising forwards and one commit: L / (T + 1) where every block runs
its T steps (0.8 at L = T = 4: what seeded weights read, whose
confidences never clear the threshold), up to L / 2 where one forward
reveals a whole block. None on a program whose events lack the counters
(one that has no block program), or for a family without the reading."""


def read(run):
    counters = getattr(getattr(run, "family", None), "denoise_counters",
                       None)
    got = counters(run) if run.kind == "serve" and counters else None
    return got["emitted"] / got["rider_forwards"] if got else None
