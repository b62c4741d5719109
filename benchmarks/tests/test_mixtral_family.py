"""The Mixtral rehearsal family (PR 27: new files only, to prove the
seam of benchmarks/families/ takes a second family) at the toy's sizes
on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, trace_parts, weights


@pytest.fixture(scope="module")
def toy():
    cfg = common.load_json("rehearsal", "toy-mixtral.json")
    fam = common.load_family(cfg["family"], cfg["kind"])
    pcfg = fam.program_config(cfg)
    model = fam.model(pcfg)
    params = fam.init_params(weights.param_shapes(model), 2**31 + 7)
    return cfg, fam, pcfg, model, params


def test_the_toy_is_mixtral_tiny(toy):
    import dataclasses
    from ray_tpu.models.mixtral import mixtral_tiny
    _cfg, _fam, pcfg, _model, _params = toy
    want = mixtral_tiny(dtype=jnp.float32, max_seq_len=512)
    assert dataclasses.asdict(pcfg) == dataclasses.asdict(want)


def test_seeded_scales(toy):
    """Expert tensors [E, D, F] get std D ** -0.5 (their fan-in), not
    E ** -0.5, which the Llama rule's shape[0] would give; the router
    0.02 as the model's own initialiser."""
    _cfg, _fam, pcfg, _model, params = toy
    moe = params["params"]["layers_0"]["moe"]
    assert moe["w1"].shape == (4, 64, 128) and moe["w2"].shape == (
        4, 128, 64)
    for name, want in (("w1", 64 ** -0.5), ("w3", 64 ** -0.5),
                       ("w2", 128 ** -0.5), ("router", 0.02)):
        got = float(jnp.std(moe[name]))
        assert abs(got - want) / want < 0.1, (name, got, want)
    assert float(jnp.std(params["params"]["tok_embeddings"])) < 0.025
    assert np.all(np.asarray(params["params"]["norm"]["scale"]) == 1.0)


def test_reference_matches_the_model(toy):
    """Float32 both sides, full forward logits: the two differ only in
    the order of their sums (the model dispatches through capacity
    buffers, drop-free at this size; the reference weights every expert
    by its gate or by zero), so they agree to rounding, rtol 1e-4. A
    softmax over all four experts instead of the selected two changes
    the gates by tens of per cent, and a dropped token loses a whole
    expert's output: either moves logits by orders more."""
    _cfg, fam, pcfg, model, params = toy
    ids = jnp.asarray(np.random.default_rng(0).integers(
        1, 255, size=(2, 40)), jnp.int32)
    want, _ = jax.jit(model.apply)(params, ids)
    rw = fam.reference_weights(params, pcfg)
    got = fam.reference_logits(rw, ids, pcfg)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5)
    # the comparison tells routings apart: the same reference with one
    # expert a token, or with all four under one softmax, is far outside
    from benchmarks.reference import mixtral as ref
    scale = float(np.abs(np.asarray(want)).max())
    for wrong_k in (1, 4):
        wrong = ref.forward(rw, ids, n_heads=pcfg.n_heads,
                            n_kv_heads=pcfg.n_kv_heads, eps=pcfg.norm_eps,
                            theta=pcfg.rope_theta, top_k=wrong_k)
        gap = float(np.abs(np.asarray(wrong) - np.asarray(want)).max())
        assert gap > 100 * 1e-4 * scale, (wrong_k, gap, scale)


def test_byte_counts_by_hand(toy):
    cfg, fam, _pcfg, _model, _params = toy
    llama = common.load_family("llama", "serve")
    base = llama.decode_step_bytes(cfg, 100.0, 4)
    # 4 slots x top-2 can touch all 4 experts: 3 more SwiGLUs a layer
    # in bf16, and the float32 router
    want = base + 2 * (3 * 3 * 64 * 128 * 2 + 64 * 4 * 4)
    assert fam.decode_step_bytes(cfg, 100.0, 4) == want
    # one slot touches two
    one = llama.decode_step_bytes(cfg, 100.0, 1)
    assert fam.decode_step_bytes(cfg, 100.0, 1) == one + 2 * (
        3 * 64 * 128 * 2 + 64 * 4 * 4)
    assert fam.kv_bytes_per_token(cfg) == 2 * 2 * 16 * 2 * 2


def test_the_mixture_is_a_part_of_its_own(toy):
    _cfg, fam, _pcfg, _model, _params = toy
    op = "jit(decode)/while/body/Mixtral/layers_1/moe/dot_general:"
    assert trace_parts.part_of(op) == "other"          # the issue's table
    assert trace_parts.part_of(op, fam.parts) == "moe"
    assert trace_parts.part_of(
        "jit(decode)/while/body/Mixtral/layers_1/attention/kv_gather/"
        "gather:", fam.parts) == "kv_gather"
