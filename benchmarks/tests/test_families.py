"""The seam a model family comes through (benchmarks/families/): every
configuration's family loads and holds what its kind of runner asks,
the moved weight makers make the parent's weights bit for bit, and the
runners, the readers and the shared files name no model."""
import glob
import hashlib
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import common, weights

REHEARSAL = os.path.join(common.HERE, "rehearsal")


def _config_files():
    """Every configuration file: BENCHMARK.json's, and the toys."""
    files = [os.path.join(common.ROOT, c["file"])
             for c in common.load_benchmark()["configs"]]
    for path in sorted(glob.glob(os.path.join(REHEARSAL, "*.json"))):
        with open(path) as f:
            if "kind" in json.load(f):          # not a traffic mix
                files.append(path)
    return files


@pytest.mark.parametrize("path", _config_files(),
                         ids=lambda p: os.path.basename(p)[:-5])
def test_a_configurations_family_loads_with_its_kinds_attributes(path):
    with open(path) as f:
        cfg = json.load(f)
    fam = common.load_family(cfg["family"], cfg["kind"])
    for attr in common.FAMILY_ATTRS[cfg["kind"]]:
        assert callable(getattr(fam, attr)), (cfg["family"], attr)
    pcfg = fam.program_config(cfg)
    assert fam.model(pcfg) is not None
    if cfg["kind"] == "serve":
        assert fam.kv_bytes_per_token(cfg) > 0
        assert fam.decode_step_bytes(cfg, 1000.0, 4) > \
            1000 * fam.kv_bytes_per_token(cfg)
        table = getattr(fam, "parts", None)
        assert table is None or set(table) <= {"wrapped", "attention",
                                               "dense"}
    else:
        assert fam.train_flops_per_token(cfg, 64) > 0
        layers, heads, head_dim = fam.attention_shape(cfg)
        assert layers > 0 and heads * head_dim > 0


def test_a_family_that_lacks_an_attribute_is_refused():
    with pytest.raises(SystemExit, match="serves no 'train'"):
        common.load_family("llama", "train")
    with pytest.raises(SystemExit, match="has no file"):
        common.load_family("no-such-family", "serve")


def test_every_rehearsal_cell_names_files_that_exist():
    cells = sorted(glob.glob(os.path.join(REHEARSAL, "cells", "*.json")))
    assert len(cells) >= 3
    real = {w["name"] for w in common.load_benchmark()["workloads"]}
    for path in cells:
        name = os.path.basename(path)[:-5]
        cell = common.load_rehearsal_cell(name)
        assert cell["name"] == name and cell["metrics_as"] in real
        cfg = common.load_json("rehearsal", cell["config"] + ".json")
        assert cfg["chips"] == cell["chips"]
        assert "loop" in common.load_json("rehearsal",
                                          cell["traffic"] + ".json")
    with pytest.raises(SystemExit, match="toy-llama.chat-sat"):
        common.load_rehearsal_cell("no-such-cell")


def _digests(tree):
    return {jax.tree_util.keystr(p):
            hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("toy", ["toy-llama", "toy-gpt2"])
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_seeded_weights_are_the_parents_bit_for_bit(toy, seed):
    """data/seeded_params.sha256.json was recorded from the parent of
    PR 27 (weights.llama_params, weights.gpt2_params) before the code
    moved into the family files: same leaf order, same fold_in(key, i),
    same scales, so a seed's weights, and with them every number a
    ``[correct]`` line prints, stay what they were."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "seeded_params.sha256.json")) as f:
        want = json.load(f)[f"{toy}/{seed}"]
    cfg = common.load_json("rehearsal", toy + ".json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    model = fam.model(fam.program_config(cfg))
    if cfg["kind"] == "serve":
        params = fam.init_params(weights.param_shapes(model), seed)
    else:
        params = fam.init_params(model, seed)
    assert _digests(params) == want


SHARED = (["run.py", "serve_runner.py", "train_runner.py", "weights.py",
           "trace_parts.py"]
          + sorted(os.path.join("metrics", f) for f in os.listdir(
              os.path.join(common.HERE, "metrics")) if f.endswith(".py")))
NAMES_A_MODEL = re.compile(
    r"ray_tpu\.models|benchmarks\.reference|costs\.llama_|costs\.gpt2_")


@pytest.mark.parametrize("rel", SHARED)
def test_shared_files_name_no_model(rel):
    """What is one family's lives in benchmarks/families/<family>.py:
    a later PR adds a family without editing any of these."""
    with open(os.path.join(common.HERE, rel)) as f:
        found = NAMES_A_MODEL.findall(f.read())
    assert not found, (rel, found)
