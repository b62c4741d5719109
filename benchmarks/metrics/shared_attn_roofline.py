"""Kernels: the least time the layers that read the one layer's K/V
pages could take for a decode step's attention on this chip, over the
time they took: jit_decode's self time under the family's
``SHARED_PARTS`` a step, the owner and its readers together (the
family's ``decode_parts_by_rounds``). The least time is the bytes they
MUST move (the family's ``shared_step_bytes``: the rounds'
``decode_shared_kv_reads``, the riders' context entries x the layers
that read pages, x a token's key and value, 5,120 B as the arithmetic
needs them) over the chip's published HBM bandwidth: two FLOPs a byte
fetched, so bytes bound it. It counts what MUST be read: a page that
keeps 16 head rows for 10 pairs (8,192 B a token as stored) reads lower
by that share, and so does a kernel that fetches a page more than once.
None without a joined trace, without peaks, for a family that has no
such count, a program that names no such scope or whose rounds lack the
counter."""


def read(run):
    fam = getattr(run, "family", None)
    if (run.kind != "serve" or run.peaks is None
            or not hasattr(fam, "shared_step_bytes")):
        return None
    got = fam.decode_parts_by_rounds(run)
    if not got or got.get("shared_reads", 0) <= 0:
        return None
    took = fam.under(got, fam.SHARED_PARTS)
    if not took:
        return None
    least_s = (fam.shared_step_bytes(run.cfg, got["shared_reads"])
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (took / got["steps"])
