"""The text a decode step's attention lowers to for the TPU, at every
serving cell's own shape, and its digest: what shows that an edit to
``ops/paged_decode_attention.py`` or to the rule in
``ops/paged_attention.py`` left the cells' ``T == 1`` programs alone.
Runs anywhere (nothing is compiled and no chip is asked for): the rule
is steered as one TPU reads it, the call is lowered for the platform,
and the Mosaic kernel's serialized body, which holds file paths and
line numbers, is parsed and printed without them.

A cell's shape is the question its engine asks of the rule
(``RoundAccounts.decode_kernel_serves``: the slots, the heads a page's
rows are asked with, one page as ``page_layout`` stores it, the table's
width), over a pool of the deployment's ``n_pages``.

  python tools/paged_decode_lowered.py            one JSON line a config
  python tools/paged_decode_lowered.py --texts D  the texts under D too,
                                                  to diff two trees'
  python tools/paged_decode_lowered.py --write    re-pin
      tests/data/paged_decode_lowered.json, which
      tests/test_paged_decode.py holds the tree to (one query a row
      only: a block's program is its PR's to change). Re-pin with a
      change that MEANS to move these programs, and measure the cells.
"""
from __future__ import annotations

import argparse
import base64
import glob
import hashlib
import json
import os
import re
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
PINS = os.path.join(ROOT, "tests", "data", "paged_decode_lowered.json")
_BODY = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'


def configs():
    """The serving configurations' names, as benchmarks/configs has
    them."""
    names = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmarks",
                                              "configs", "*.json"))):
        with open(path) as f:
            if json.load(f)["kind"] == "serve":
                names.append(os.path.basename(path)[:-len(".json")])
    return names


def question(name: str):
    """``paged_decode.applies``'s arguments as ``name``'s engine asks
    them for its decode step, the page widened to the deployment's pool
    (None: the configuration has no layer the rule is asked about)."""
    import jax

    from benchmarks import common
    from ray_tpu.ops import paged_decode_attention as pd
    from ray_tpu.serve.round_accounts import RoundAccounts
    cfg = common.load_json("configs", name + ".json")
    pcfg = common.load_family(cfg["family"], cfg["kind"]).program_config(cfg)
    dep = cfg["deployment"]
    page_size, n_pages = dep["page_size"], dep["n_pages"]
    accounts = RoundAccounts(
        pcfg, {}, [], slots=dep["max_slots"], page_size=page_size,
        max_pages=min(n_pages - 1, -(-pcfg.max_seq_len // page_size)),
        kv_dtype=dep.get("kv_dtype", "fp"), mesh=None)
    asked = []
    with mock.patch.object(pd, "applies",
                           lambda *a: asked.append(a) or True):
        accounts.decode_kernel_serves()
    if not asked:
        return None
    q, k, v, sk, table, value_dim, block_len = asked[0]

    def pool(page):
        return page and jax.ShapeDtypeStruct((n_pages,) + page.shape[1:],
                                             page.dtype)
    return q, pool(k), pool(v), sk, table, value_dim, block_len


def lowered(q, pk, pv, sk, table, value_dim, block_len):
    """(the text of one ``_paged_window_attention`` call of these
    shapes lowered for a TPU that the rule reads as one chip, the
    kernels in it)."""
    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import paged_decode_attention as pd
    assert sk is None, "an int8 pool is the loop's"
    pos = jax.ShapeDtypeStruct(q.shape[:1], jnp.int32)

    def call(q, pk, pv, table, pos):
        # (a latent family's own scale: A.X-K1's)
        return pa._paged_window_attention(
            q, pk, pv, None, None, table, pos, value_dim=value_dim,
            block_len=block_len,
            **({"softmax_scale": 0.1309} if pv is None else {}))
    with mock.patch.object(pd, "_on_one_tpu", lambda: True):
        text = jax.jit(call).trace(q, pk, pv, table, pos).lower(
            lowering_platforms=("tpu",)).as_text()
    bodies = []
    for found in re.finditer(_BODY, text):
        with jax_mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            bodies.append(ir.Module.parse(
                base64.b64decode(found.group(1)), ctx
            ).operation.get_asm(enable_debug_info=False))
    return re.sub(_BODY, "body: <below>", text) + "\n".join(bodies), \
        len(bodies)


def reading(name: str, texts: str = ""):
    """One configuration's line: its question's shapes, whether the
    rule hands it to the kernel, and the lowered text's digest."""
    from ray_tpu.ops import paged_decode_attention as pd
    asked = question(name)
    if asked is None:
        return {"config": name, "asks": False}
    q, pk, pv, sk, table, value_dim, block_len = asked
    with mock.patch.object(pd, "_on_one_tpu", lambda: True):
        serves = pd.applies(q, pk, pv, sk, table, value_dim, block_len)
    line = {"config": name, "asks": True, "q": list(q.shape),
            "pool": list(pk.shape), "latent": pv is None,
            "table": list(table.shape), "block_len": block_len,
            "kernel": bool(serves)}
    if sk is None:
        text, kernels = lowered(*asked)
        line.update(kernels=kernels,
                    sha256=hashlib.sha256(text.encode()).hexdigest())
        if texts:
            os.makedirs(texts, exist_ok=True)
            with open(os.path.join(texts, name + ".txt"), "w") as f:
                f.write(text)
    return line


def main():
    import jax
    ap = argparse.ArgumentParser()
    ap.add_argument("--texts", default="")
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()
    lines = [reading(name, args.texts) for name in configs()]
    for line in lines:
        print(json.dumps(line), flush=True)
    if args.write:
        with open(PINS, "w") as f:
            json.dump({"jax": jax.__version__, "sha256": {
                line["config"]: line["sha256"] for line in lines
                if line.get("kernel") and line["q"][1] == 1}}, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
