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


def _last_line(out):
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1          # one result, and it is the last line
    assert out.stdout.strip().splitlines()[-1] == lines[0]
    return json.loads(lines[0])


@pytest.mark.parametrize("cell,e2e,per_layer", [
    ("toy-llama.chat-r80", "ttft_p50_ms itl_p50_ms setup_s",
     "ttft_p95_ms queue_wait_p95_ms prefill_in_slot_p50_ms "
     "prefill_budget_share"),
    ("toy-llama.chat-sat", "serve_tokens_per_s setup_s",
     "kv_peak_share host_gap_share decode_riders_mean round_host_ms"),
    ("toy-gpt2.train", "train_tokens_per_s setup_s", "train_mfu"),
])
def test_trace_2_measures_then_traces(cell, e2e, per_layer):
    out = _run("--rehearse", "--workload", cell, "--seed",
               str(2**31 + 11), "--seconds", "3", "--trace", "2")
    line = _last_line(out)
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compiles_in_window", "breakdown"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    # both kinds of metric side by side, every end-to-end value there
    for name in e2e.split():
        assert line["metrics"][name]["value"] > 0, name
    for name in per_layer.split():
        if name == "train_mfu":      # needs the chip's peak: not on a CPU
            continue
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
