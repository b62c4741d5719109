"""Train step (train/spmd.py): this run's tokens per second per chip
times the FLOPs a token needs (the family's ``train_flops_per_token``,
counted in benchmarks/costs.py: 6N + attention, no recomputation
counted) over the chip's published bf16 peak."""


def read(run):
    if run.kind != "train" or run.peaks is None:
        return None
    fpt = run.family.train_flops_per_token(run.cfg, run.seq)
    return (100.0 * run.e2e["train_tokens_per_s"] * fpt
            / run.peaks["bf16_flops"])
