"""Model step: of the device time of jit_prefill (the chunked-prefill
program: four rows of a chunk each), the share under the SLIDING-WINDOW
layers' attention (the family's ``SLIDING_PARTS``: the chunk appended
to the rows' rings and attended over them, and the ring scatters that
the compiler flattens into unnamed fusions: the family's
``ring_copies``): what six layers of eight cost a call when each scores
a window and a chunk of keys, not the context. Needs the trace itself (``run.trace_dir``, --trace 2); None
for a family without such parts or a program that names none."""


def read(run):
    fam = getattr(run, "family", None)
    if run.kind != "serve" or not hasattr(fam, "SLIDING_PARTS"):
        return None
    got = fam.typed_parts(run, "jit_prefill")
    if not got or not got["module_s"]:
        return None
    return 100.0 * fam.sliding_s(got) / got["module_s"]
