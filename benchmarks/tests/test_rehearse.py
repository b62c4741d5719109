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


@pytest.mark.parametrize("cell,trace,metric", [
    ("toy-llama.chat-r80", "0", "ttft_p50_ms itl_p50_ms"),
    ("toy-llama.chat-r80", "1", "ttft_p95_ms"),
    ("toy-llama.chat-sat", "1", "kv_peak_share"),
    ("toy-gpt2.train", "0", "train_tokens_per_s"),
])
def test_rehearsal_prints_the_contract_line(cell, trace, metric):
    out = _run("--rehearse", "--workload", cell, "--seed",
               str(2**31 + 11), "--seconds", "3", "--trace", trace)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    for name in metric.split():
        assert set(line["metrics"][name]) == {"value", "unit"}
    if trace == "0":
        assert line["metrics"]["setup_s"]["value"] > 0
    assert line["compiles_in_window"] == 0


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
