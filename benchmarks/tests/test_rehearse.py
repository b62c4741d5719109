"""--rehearse end to end prints a line of the contract's shape; cells
themselves refuse to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks import common


def _run(*args):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=common.ROOT,
        env=env, capture_output=True, text=True, timeout=600)


def _cells():
    """Every toy cell under rehearsal/cells/: a new file is a new case.
    ``reports`` lists the per-layer metrics a CPU rehearsal of the cell
    must show (those that need neither the chip's peaks nor a device
    in the trace)."""
    cell_dir = os.path.join(common.HERE, "rehearsal", "cells")
    return [common.load_rehearsal_cell(f[:-5])
            for f in sorted(os.listdir(cell_dir)) if f.endswith(".json")]


CELLS = {c["name"]: c for c in _cells()}
BENCH = common.load_benchmark()


def _e2e_names(cell):
    return [m["name"] for m in common.metrics_of_cell(
        BENCH, "end_to_end", CELLS[cell]["metrics_as"])]


@pytest.mark.parametrize("cell,trace", [(c, "0") for c in CELLS] + [
    ("toy-llama.chat-r80", "1"), ("toy-llama.chat-sat", "1")])
def test_rehearsal_prints_the_contract_line(cell, trace):
    out = _run("--rehearse", "--workload", cell, "--seed",
               str(2**31 + 11), "--seconds", "3", "--trace", trace)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    wanted = _e2e_names(cell) if trace == "0" else CELLS[cell]["reports"]
    assert wanted
    for name in wanted:
        assert set(line["metrics"][name]) == {"value", "unit"}
    if trace == "0":
        assert line["metrics"]["setup_s"]["value"] > 0
    assert line["compiles_in_window"] == 0


def _last_line(out):
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1          # one result, and it is the last line
    assert out.stdout.strip().splitlines()[-1] == lines[0]
    return json.loads(lines[0])


@pytest.mark.parametrize("cell", list(CELLS))
def test_trace_2_measures_then_traces(cell):
    out = _run("--rehearse", "--workload", cell, "--seed",
               str(2**31 + 11), "--seconds", "3", "--trace", "2")
    line = _last_line(out)
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compiles_in_window", "breakdown"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    # both kinds of metric side by side, every end-to-end value there
    for name in _e2e_names(cell):
        assert line["metrics"][name]["value"] > 0, name
    for name in CELLS[cell]["reports"]:
        assert set(line["metrics"][name]) == {"value", "unit"}, name
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "[traced phase]" in out.stdout
    # the trace is deleted once it is reduced
    assert not os.path.exists(os.path.join(
        common.ROOT, ".bench_trace", cell))


def test_trace_2_reports_the_end_to_end_keys_of_trace_0():
    args = ("--rehearse", "--workload", "toy-gpt2.train", "--seed", "5",
            "--seconds", "2")
    plain = _last_line(_run(*args, "--trace", "0"))
    both = _last_line(_run(*args, "--trace", "2"))
    assert set(plain) | {"breakdown"} == set(both)
    assert set(plain["metrics"]) <= set(both["metrics"])
    for name, m in plain["metrics"].items():
        assert both["metrics"][name]["unit"] == m["unit"]
    assert set(plain["device"]) <= set(both["device"])


def test_a_cell_refuses_the_cpu():
    out = _run("--workload", "gpt2-124m.train-b24", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")


def test_selectors_are_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_PAGED_KERNEL="1")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-gpt2.train"], cwd=common.ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and "RAY_TPU_PAGED_KERNEL" in out.stderr
