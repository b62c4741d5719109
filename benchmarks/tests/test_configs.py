"""Each configuration file against its source's published sizes
(published/<config>.json: the source's ``config.json`` keys as the
source or the catalog gives them; a configuration without one fails),
and BENCHMARK.json against the files and the contract's shape."""
import json
import os
import re

import pytest

from benchmarks import common

# what may be cut: depth, and the context a chip's share holds
REDUCIBLE = {"num_hidden_layers", "max_position_embeddings", "n_layer"}
WIDTH = re.compile(r"_size$|_dim$|_rank$|^n_embd$|expan|experts_per")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return common.load_benchmark()


def published(config: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "published", config + ".json")
    assert os.path.exists(path), (
        f"configuration {config} has no published sizes at {path}")
    with open(path) as f:
        return json.load(f)


def test_files_hold_the_published_sizes_but_for_reduced(bench):
    for conf in bench["configs"]:
        with open(os.path.join(common.ROOT, conf["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == conf["reduced"]
        source = published(conf["name"])
        for key, want in source.items():
            if key in conf["reduced"]:
                assert cfg[key] != want, key
                assert cfg["reduced_from"][key] == want, key
                assert not WIDTH.search(key), f"{key} is a width"
                assert key in REDUCIBLE, f"{key} is no cut of scale"
            else:
                assert cfg[key] == want, (conf["name"], key)
        # what the program cannot express keeps its published value
        # and is named, never listed as reduced
        for key in cfg.get("unsupported_by_program", {}):
            assert key not in conf["reduced"]
            assert cfg[key] == source[key], key
        assert cfg["chips"] in (1, 4)


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert bench["trace_in_run"] is True     # the harness takes --trace 2
    assert 1 <= bench["run_seconds"] <= 51
    cells = [w["name"] for w in bench["workloads"]]
    confs = {c["name"]: c for c in bench["configs"]}
    assert len(set(cells)) == len(cells)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in confs and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            common.HERE, "traffic", w["traffic"] + ".json"))
        with open(os.path.join(common.ROOT,
                               confs[w["config"]]["file"])) as f:
            assert json.load(f)["chips"] == w["chips"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for cell in cells:
        own = common.metrics_of_cell(bench, "end_to_end", cell)
        assert len(own) >= 2, cell
        assert common.metrics_of_cell(bench, "per_layer", cell), cell
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and "bound" not in m
        assert callable(common.load_metric_reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (
                f"{m['name']} moves {m['moves']}, which {cell} does not "
                f"report")


def test_every_metric_has_a_reader(bench):
    names = {m["name"] for m in bench["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(common.HERE,
                                                     "metrics"))
             if f.endswith(".py")}
    assert files == names


def test_peaks_table():
    p = common.peaks_for("TPU v5 lite")
    assert p == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}
    with pytest.raises(SystemExit):
        common.peaks_for("cpu")
