"""The OLMoE family (families/olmoe.py, reference/olmoe.py, the toy
``rehearsal/toy-olmoe.json``) on the CPU: the program against the plain
reference at the toy's sizes, the byte counts against a hand count, the
four readers the cell adds on a hand-made trace and hand-made rounds,
and the rehearsal cell end to end."""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, trace_parts, weights


@pytest.fixture(scope="module")
def olmoe_toy():
    cfg = common.load_json("rehearsal", "toy-olmoe.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    return cfg, fam, pcfg, model, params


def test_the_toy_is_olmoe_tiny(olmoe_toy):
    import dataclasses
    from ray_tpu.models.mixtral import olmoe_tiny
    _cfg, _fam, pcfg, _model, _params = olmoe_toy
    want = olmoe_tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=512)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)


def test_the_published_configuration_is_olmoe_1b_7b_at_8_layers():
    import dataclasses
    from ray_tpu.models.mixtral import olmoe_1b_7b
    cfg = common.load_json("configs", "olmoe-1b-7b-0125-d8.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    want = olmoe_1b_7b(n_layers=8, param_dtype=jnp.bfloat16)
    assert dataclasses.asdict(fam.program_config(cfg)) == \
        dataclasses.asdict(want)
    assert fam.kv_bytes_per_token(cfg) == 65536       # Mistral-d16's


def test_a_program_that_cannot_express_olmoe_is_refused(olmoe_toy,
                                                         monkeypatch):
    """The parent's MixtralConfig has no norm_topk_prob, qk_norm or
    tie_word_embeddings: the family must exit before a weight is made,
    not serve a Mixtral-ruled model."""
    import dataclasses
    import ray_tpu.models.mixtral as mx
    cfg, fam, *_ = olmoe_toy

    @dataclasses.dataclass(frozen=True)
    class ParentsConfig:
        vocab_size: int = 32000
        num_experts: int = 8
    monkeypatch.setattr(mx, "MixtralConfig", ParentsConfig)
    with pytest.raises(SystemExit, match="cannot express OLMoE"):
        fam.program_config(cfg)


def test_what_the_program_lacks_is_refused(olmoe_toy):
    cfg, fam, *_ = olmoe_toy
    for wrong in ({"clip_qkv": 8.0}, {"attention_bias": True},
                  {"rope_scaling": {"type": "linear"}}, {"head_dim": 8}):
        with pytest.raises(SystemExit):
            fam.program_config({**cfg, **wrong})


def test_olmoe_reference_matches_the_model(olmoe_toy):
    """Float32 both sides, full forward logits: the two differ only in
    the order of their sums (the program sorts the pairs by expert and
    multiplies group by group; the reference computes every expert on
    every token), so they agree to rounding, rtol 1e-4. The head is the
    program's own lm_head: a reference handed the embedding instead is
    far outside, and so is one with 2 or 8 experts a token."""
    _cfg, fam, pcfg, model, params = olmoe_toy
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 40)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_logits(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)
    from benchmarks.reference import olmoe as ref
    scale = float(np.abs(np.asarray(want)).max())
    sizes = dict(n_heads=pcfg.n_heads, n_kv_heads=pcfg.n_kv_heads,
                 eps=pcfg.norm_eps, theta=pcfg.rope_theta)
    wrongs = [ref.forward(rw, ids, top_k=k, **sizes) for k in (2, 8)]
    wrongs.append(ref.forward({**rw, "head": rw["embed"]}, ids,
                              top_k=pcfg.num_experts_per_tok, **sizes))
    for wrong in wrongs:
        gap = float(np.abs(np.asarray(wrong) - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (gap, scale)


def test_the_reference_imports_nothing_of_the_programs_models():
    for name in ("olmoe.py", "llama.py"):
        with open(os.path.join(common.HERE, "reference", name)) as f:
            assert "ray_tpu" not in f.read().replace(
                "ray_tpu.models.mixtral", "")    # only in prose, if at all
    import benchmarks.reference.olmoe as ref
    assert not any(m.startswith("ray_tpu") for m in (
        getattr(v, "__module__", "") or "" for v in vars(ref).values()))


def test_olmoe_byte_counts_by_hand(olmoe_toy):
    cfg, fam, _pcfg, _model, _params = olmoe_toy
    llama = common.load_family("llama", "serve")
    # toy: hidden 64, expert width 32, 8 experts, 3 a token, 2 layers,
    # 4 + 4 heads of 16
    one_expert = 3 * 64 * 32 * 2
    assert fam.expert_bytes(cfg) == one_expert
    base = llama.decode_step_bytes(cfg, 100.0, 4)
    router, qk = 64 * 8 * 4, (4 + 4) * 16 * 4
    # 5.5 experts touched a layer, by the counter: the Llama count's one
    # dense SwiGLU of the same width goes, 5.5 experts, the float32
    # router and the two norms' scales come
    want = base + 2 * (5.5 * one_expert - one_expert + router + qk)
    assert fam.decode_step_bytes(cfg, 100.0, 4,
                                 experts_touched=5.5) == want
    # without a counter: the most 4 rows x 3 can touch, all 8
    assert fam.decode_step_bytes(cfg, 100.0, 4) == \
        base + 2 * (7 * one_expert + router + qk)
    assert fam.decode_step_bytes(cfg, 100.0, 1) == \
        llama.decode_step_bytes(cfg, 100.0, 1) + 2 * (
            2 * one_expert + router + qk)
    # a layer-step's experts: 5.5 experts' matrices and 9 rows in, out
    assert fam.experts_step_bytes(cfg, 5.5, 9) == \
        5.5 * one_expert + 2 * 9 * 64 * 2
    assert fam.experts_step_flops(cfg, 9) == 2 * 3 * 9 * 64 * 32
    assert fam.kv_bytes_per_token(cfg) == 2 * 4 * 16 * 2 * 2


def test_the_mixtures_scopes_are_parts_of_their_own(olmoe_toy):
    _cfg, fam, *_ = olmoe_toy
    base = "jit(decode)/while/body/Mixtral/layers_1/"
    for scope in fam.MOE_SCOPES:
        assert trace_parts.part_of(
            base + f"moe/{scope}/dot_general:", fam.parts) == scope
    assert trace_parts.part_of(base + "moe/convert:", fam.parts) == "moe"
    assert trace_parts.part_of(base + "attention/q_norm/mul:",
                               fam.parts) == "norms"
    assert trace_parts.part_of(base + "attention/k_norm/rsqrt:",
                               fam.parts) == "norms"
    assert trace_parts.part_of(base + "attention/wq/dot_general:",
                               fam.parts) == "projections"
    assert trace_parts.part_of(base + "attention/kv_gather/gather:",
                               fam.parts) == "kv_gather"
    assert trace_parts.part_of(base + "attention/mul:",
                               fam.parts) == "rope"


# -------------------------------------------------- the four new readers

def _round(t, **data):
    base = {"host_gap_s": 1e-4, "wall_s": 0.1, "overlap": True,
            "decode_riders": 12, "decode_steps": 8}
    base.update(data)
    return (0, t, "round", None, None, base)


def _traced_run(toy, tmp_path):
    """A hand-made --trace 2 run: one jit_decode run of 4 steps (the
    ten heaviest operations run 4 times each) and one jit_prefill run,
    their operations named by scope; rounds whose decode counters say
    5 experts touched and 9 pairs a layer-step."""
    cfg, fam, *_ = toy
    dec = "jit(decode)/while/body/Mixtral/layers_0/"
    pre = "jit(prefill)/Mixtral/layers_0/"
    ops, t = [], 0
    body = [("moe/moe_router/dot_general:", 10),
            ("moe/moe_dispatch/sort:", 30),
            ("moe/moe_experts/ragged_dot:", 100),
            ("moe/moe_combine/gather:", 20),
            ("attention/kv_gather/gather:", 40),
            ("attention/wq/dot_general:", 50)]
    for step in range(4):
        for i, (scope, dur) in enumerate(body):
            ops.append([f"%f.{i} = f32[8] fusion(", t, dur, dec + scope])
            t += dur
    end_decode = t
    for i, (scope, dur) in enumerate(
            [("moe/moe_experts/ragged_dot:", 300),
             ("moe/moe_dispatch/sort:", 100)]):
        ops.append([f"%p.{i} = f32[8] fusion(", t, dur, pre + scope])
        t += dur
    ir = {"modules": [["jit_decode(1)", 0, end_decode],
                      ["jit_prefill(2)", end_decode, t - end_decode]],
          "ops": ops}
    module_ops = {f"f.{i}": [4, 4 * dur / 1e9, "fusion"]
                  for i, (_s, dur) in enumerate(body)}
    counters = dict(moe_decode_experts_touched=5 * 2 * 4,
                    moe_decode_pairs=9 * 2 * 4,
                    moe_decode_layer_steps=2 * 4,
                    moe_experts_touched=5 * 2 * 4 + 8 * 2,
                    moe_layer_steps=2 * 4 + 2, moe_pairs=9 * 2 * 4 + 96,
                    moe_load_max=3 * 8 + 30)
    run = types.SimpleNamespace(
        kind="serve", cfg=cfg, family=fam, trace_dir=str(tmp_path),
        window=(0.5, 8.0), trace_span=(10.0, 14.0),
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
        events=[_round(1.0, **counters), _round(2.0, **counters),
                _round(11.0, **counters)],
        trace={"modules": {"jit_decode": {"runs": 1,
                                          "seconds": end_decode / 1e9}},
               "module_ops": {"jit_decode": module_ops}})
    run._trace_parts = {"ir": ir}
    return run


def test_the_four_readers_on_a_hand_made_run(olmoe_toy, tmp_path):
    cfg, fam, *_ = olmoe_toy
    run = _traced_run(olmoe_toy, tmp_path)
    read = common.load_metric_reader
    # a step holds 10 + 30 + 100 + 20 ns under the mixture's scopes
    assert read("decode_moe_ms")(run) == pytest.approx(160e-6)
    assert trace_parts.decode_step_parts(run)["step_ms"] == \
        pytest.approx(250e-6)
    # decode 4 x 160 + prefill 400 under the mixture; experts 4 x 100 +
    # 300 of it
    assert read("moe_dispatch_share")(run) == pytest.approx(
        100.0 * (1040 - 700) / 1040)
    # 5 experts and 9 pairs a layer-step, 2 layers: the experts took
    # 100 ns a step = 50 ns a layer-step
    least_s = fam.experts_step_bytes(cfg, 5, 9) / 819e9
    assert fam.experts_step_flops(cfg, 9) / 197e12 < least_s
    assert read("moe_experts_roofline")(run) == pytest.approx(
        100.0 * least_s / 50e-9)
    # the window's two rounds: (40 + 16) touched over 10 layer-steps
    assert read("moe_experts_touched_mean")(run) == pytest.approx(5.6)


def test_the_readers_find_nothing_where_there_is_nothing(olmoe_toy,
                                                         tmp_path):
    """A dense family, a program without the scopes or the counters, a
    run without a trace: None, never an error."""
    run = _traced_run(olmoe_toy, tmp_path)
    read = common.load_metric_reader
    names = ("decode_moe_ms", "moe_dispatch_share",
             "moe_experts_roofline", "moe_experts_touched_mean")
    dense = types.SimpleNamespace(**{
        **vars(run), "family": common.load_family("llama", "serve"),
        "events": [_round(1.0)]})
    for name in names:
        assert read(name)(dense) is None, name
    bare = types.SimpleNamespace(**{**vars(run), "events": [_round(1.0)]})
    del bare.trace_dir, bare._trace_parts
    for name in names:
        assert read(name)(bare) is None, name
    unnamed = _traced_run(olmoe_toy, tmp_path)
    for op in unnamed._trace_parts["ir"]["ops"]:
        op[3] = op[3].replace("moe_", "x_")
    for name in names[:3]:
        assert read(name)(unnamed) is None, name


# ------------------------------------------------------ the rehearsal cell

def _rehearse(trace):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("RAY_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--rehearse",
         "--workload", "toy-olmoe.chat-sat", "--seed", str(2**31 + 13),
         "--seconds", "3", "--trace", trace], cwd=common.ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("trace", ["0", "2"])
def test_the_rehearsal_cell_runs(trace):
    """The toy cell borrows olmoe-d8.chat-sat's metric lists: correct
    against the OLMoE reference through the served path, no program
    built in the window, and (--trace 2) the counter metric there; the
    three device_trace metrics need a device in the trace, which a CPU
    has not (the hand-made run above checks their readers)."""
    line, stdout = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["compiles_in_window"] == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    if trace == "2":
        touched = line["metrics"]["moe_experts_touched_mean"]
        assert touched["unit"] == "experts"
        # 3 a token of 8: a step touches at least 3 and at most all 8
        assert 3.0 <= touched["value"] <= 8.0
        for name in ("host_gap_share", "decode_riders_mean",
                     "round_host_ms", "kv_peak_share"):
            assert name in line["metrics"], name
        assert "decode_roofline" not in line["metrics"]
        assert "moe_pairs" in stdout            # the engine's counters
