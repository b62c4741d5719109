"""Tensor-parallel sharded serving engine (serve/sharding.py).

Parity discipline: the SAME model + params served by a 1-chip engine
and a 4-way tensor-parallel engine (forced multi-device CPU host
mesh) must emit token-IDENTICAL greedy outputs on every serving path
— plain decode, prefix-cache hit resume, and spec-decode
accept/rollback. fp32 tiny configs on purpose: the TP psum splits
each layer's reduction, and under bf16 output rounding a borderline
argmax tie could flip a token without anything being wrong; at fp32
ties are vanishingly unlikely, so any mismatch is a real bug.

Plus the placement/validation units: head-sharded KV pool layout,
strict match_partition_rules unmatched-path reporting, divisibility
errors, replica device groups, and paged_append's typed shape errors.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import (Llama, llama_tiny,
                                  llama_sharding_rules,
                                  llama_tp_validate)
from ray_tpu.serve.engine import LLMEngine
from ray_tpu.serve.sharding import (EngineSharding,
                                    ShardingConfigError,
                                    replica_device_groups)


@pytest.fixture(scope="module")
def tiny():
    # n_kv_heads=4 so heads divide tp=4 (llama_tiny defaults to 2)
    cfg = llama_tiny(n_kv_heads=4, dtype=jnp.float32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


@pytest.fixture(scope="module")
def tp4(tiny, cpu_mesh_devices):
    cfg, _, _ = tiny
    return EngineSharding.build(cfg, tp=4,
                                devices=cpu_mesh_devices[:4])


def _engine(tiny, sharding, **kw):
    _, model, params = tiny
    opts = dict(max_slots=4, page_size=8, n_pages=96, chunk=4,
                prefill_chunk=16, temperature=0.0, seed=0)
    opts.update(kw)
    eng = LLMEngine(model, params, sharding=sharding, **opts)
    eng.start()
    return eng


# ------------------------------------------------------ parity paths

def test_plain_decode_parity_tp1_vs_tp4(tiny, tp4):
    cfg = tiny[0]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size - 1, size=12).tolist()
               for _ in range(6)]

    def run(sh):
        eng = _engine(tiny, sh)
        hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        outs = [h.result() for h in hs]
        eng.shutdown()
        return outs

    assert run(None) == run(tp4)


def test_prefix_cache_hit_resume_parity(tiny, tp4):
    """Request 1 warms the radix cache; later requests resume
    mid-prompt off shared pages. The hit path (boundary-page COW copy
    + mid-offset prefill) must be token-identical across tp widths —
    and must actually HIT on both, or the test proves nothing."""
    cfg = tiny[0]
    rng = np.random.RandomState(1)
    shared = rng.randint(1, cfg.vocab_size - 1, size=32).tolist()
    tails = [rng.randint(1, cfg.vocab_size - 1, size=6).tolist()
             for _ in range(3)]

    def run(sh):
        eng = _engine(tiny, sh, prefix_cache=True)
        outs = [eng.submit(shared + t, max_new_tokens=12).result()
                for t in tails]  # sequential: later ones hit
        hits = eng.stats.get("cache_hit_admissions", 0)
        eng.shutdown()
        return outs, hits

    base, base_hits = run(None)
    tp, tp_hits = run(tp4)
    assert base == tp
    assert base_hits >= 1 and tp_hits == base_hits


class _Scripted:
    """Proposer seam (same as tests/test_spec_decode.py): proposes a
    fixed continuation script keyed on tokens generated so far. Host-
    side and identical across tp widths, so it isolates the DEVICE
    side of speculation — the sharded verify + KV-frontier
    rollback."""

    def __init__(self, prompt_len, script):
        self.prompt_len = prompt_len
        self.script = script
        self._done = 0

    def sync(self, context):
        self._done = len(context) - self.prompt_len

    def propose(self, k):
        return self.script[self._done:self._done + k]


SPEC_KEYS = ("spec_accepted", "spec_rejected", "spec_proposed")


def _spec_run(tiny, sh, prompt, overlap, proposer=None):
    """One request through a speculating engine: its tokens and the
    accept counters. The counters are read after ``shutdown()``, when
    the loop's thread has left its last round."""
    eng = _engine(tiny, sh, spec_len=4, spec_proposer=proposer,
                  overlap=overlap)
    out = eng.submit(prompt, max_new_tokens=16).result()
    eng.shutdown()
    return out, {k: eng.stats.get(k, 0) for k in SPEC_KEYS}


# Under the overlapped loop the proposer reads the STALE frontier
# (``req.generated`` as far as the readbacks have come when the round is
# planned: serve/engine.py ``_propose_spec_locked``), so WHICH rounds
# speculate, and with them the counters, follow how fast the device
# finished the dispatch before: a race by design, which changes no
# token. The counters are compared under the lockstep loop, which
# drains before it plans; the tokens under both.
@pytest.mark.parametrize("overlap", [False, True],
                         ids=["lockstep", "overlapped"])
def test_spec_decode_accept_parity(tiny, tp4, overlap):
    """Repetitive prompt: prompt-lookup drafts get accepted. The
    verify argmax runs through the sharded psum path; the tokens agree
    exactly across tp widths, and so do the accept counters wherever
    the schedule is the same (the lockstep loop)."""
    rep = ([5, 6, 7, 8] * 8)[:24]
    base, base_stats = _spec_run(tiny, None, rep, overlap)
    tp, tp_stats = _spec_run(tiny, tp4, rep, overlap)
    assert base == tp
    for st in (base_stats, tp_stats):
        assert (st["spec_accepted"] + st["spec_rejected"]
                == st["spec_proposed"])
    if not overlap:
        assert base_stats == tp_stats
        assert base_stats["spec_accepted"] >= 1


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["lockstep", "overlapped"])
def test_spec_decode_full_rejection_rollback_parity(tiny, tp4, overlap):
    """Anti-oracle proposer: every draft is guaranteed wrong, so
    every verify rejects everything and clamps the KV write frontier
    back. Under tp=4 the rollback is a host-side position clamp over
    the head-sharded pool (device-local, no collectives) — the
    continuation must still be token-identical to the 1-chip
    engine."""
    cfg = tiny[0]
    prompt = [5, 9, 2, 7, 11]
    # n-gram default, plain reference
    ref, _ = _spec_run(tiny, None, prompt, overlap)
    wrong = [(t + 1) % cfg.vocab_size for t in ref]

    def scripted():
        return _Scripted(len(prompt), wrong)
    base, base_stats = _spec_run(tiny, None, prompt, overlap, scripted)
    tp, tp_stats = _spec_run(tiny, tp4, prompt, overlap, scripted)
    assert base == ref         # rollback preserved greedy output
    assert tp == ref
    for st in (base_stats, tp_stats):
        assert (st["spec_accepted"] + st["spec_rejected"]
                == st["spec_proposed"])
    if not overlap:
        # (a script read at a stale position may propose a right token)
        assert base_stats == tp_stats
        assert base_stats["spec_rejected"] >= 4
        assert base_stats["spec_accepted"] == 0


def test_mixtral_expert_parallel_parity(cpu_mesh_devices):
    """Mixtral on a 2-D expert x tensor mesh (ep=2 x tp=2): routing
    and the drop-free dispatch/combine run expert-sharded, attention
    head-sharded — still token-identical to the 1-chip engine."""
    from ray_tpu.models.mixtral import Mixtral, mixtral_tiny
    cfg = mixtral_tiny(dtype=jnp.float32)
    model = Mixtral(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    sh = EngineSharding.build(cfg, tp=2, ep=2,
                              devices=cpu_mesh_devices[:4])
    prompts = [np.random.RandomState(3).randint(
        1, cfg.vocab_size - 1, size=10).tolist()]

    def run(sharding):
        eng = LLMEngine(model, params, max_slots=2, page_size=8,
                        n_pages=32, chunk=4, prefill_chunk=16,
                        temperature=0.0, seed=0, sharding=sharding)
        eng.start()
        outs = [eng.submit(p, max_new_tokens=12).result()
                for p in prompts]
        eng.shutdown()
        return outs

    assert run(None) == run(sh)


# ------------------------------------------------- placement + units

def test_kv_pool_is_head_sharded(tiny, tp4):
    """The engine's page-major pool [n_pages, Pg, KH, D] must shard
    axis 2 (kv heads) over ``tensor`` and nothing else — the
    invariant that keeps paged_append / decode / page copies
    collective-free."""
    eng = _engine(tiny, tp4)
    try:
        for pk, pv in eng.pages:
            for t in (pk, pv):
                assert t.shape[2] == tiny[0].n_kv_heads
                spec = tuple(t.sharding.spec) + (None,) * 4
                assert spec[2] == "tensor"
                assert all(s is None for i, s in enumerate(spec)
                           if i != 2)
                # per-device shard holds KH/tp heads, ALL pages
                shard_shape = t.sharding.shard_shape(t.shape)
                assert shard_shape[2] == t.shape[2] // 4
                assert shard_shape[:2] == t.shape[:2]
                assert shard_shape[3] == t.shape[3]
    finally:
        eng.shutdown()


def test_dispatch_state_replicated(tiny, tp4):
    eng = _engine(tiny, tp4)
    try:
        for t in (eng._dev_cur, eng._dev_pos, eng._rng):
            assert t.sharding.is_fully_replicated
    finally:
        eng.shutdown()


def test_load_report_carries_tp(tiny, tp4):
    eng = _engine(tiny, tp4)
    try:
        assert eng.load_report()["tp"] == 4
    finally:
        eng.shutdown()
    eng = _engine(tiny, None)
    try:
        assert eng.load_report()["tp"] == 1
    finally:
        eng.shutdown()


def test_divisibility_errors():
    cfg = llama_tiny()           # n_kv_heads=2: tp=4 can't divide
    with pytest.raises(ShardingConfigError, match="n_kv_heads"):
        EngineSharding.build(cfg, tp=4)
    llama_tp_validate(cfg, 2)    # 2 divides everything: fine
    with pytest.raises(ValueError, match="n_heads|n_kv_heads"):
        llama_tp_validate(cfg, 3)
    with pytest.raises(ShardingConfigError, match="devices"):
        EngineSharding.build(llama_tiny(n_kv_heads=4), tp=4,
                             devices=jax.devices()[:2])
    with pytest.raises(ShardingConfigError, match="MoE"):
        EngineSharding.build(cfg, tp=2, ep=2)  # ep on a dense model


def test_replica_device_groups(cpu_mesh_devices):
    groups = replica_device_groups(2, 4, cpu_mesh_devices)
    assert [len(g) for g in groups] == [4, 4]
    assert set(groups[0]).isdisjoint(groups[1])
    # exhausted devices wrap around (CPU host-mesh pool testing)
    groups = replica_device_groups(3, 4, cpu_mesh_devices)
    assert groups[2] == groups[0]
    with pytest.raises(ShardingConfigError):
        replica_device_groups(1, 16, cpu_mesh_devices)


def test_match_partition_rules_unmatched_raises(tiny):
    """A >=2-D tensor no rule covers must raise (silent replication
    is the failure mode this gate exists for); 1-D norm scales fall
    through legitimately."""
    from ray_tpu.mesh.sharding import (ShardingRules,
                                       match_partition_rules)
    _, _, params = tiny
    rules = ShardingRules([(r"attention/w[qkv]/kernel",
                            P(None, "tensor"))])
    with pytest.raises(ValueError) as ei:
        match_partition_rules(rules, params)
    assert "feed_forward" in str(ei.value)   # names the culprits
    assert "REPLICATED" in str(ei.value)
    # warn mode still returns specs
    with pytest.warns(UserWarning, match="REPLICATED"):
        specs = match_partition_rules(rules, params,
                                      on_unmatched="warn")
    assert specs is not None
    # full serving rules cover every matrix: strict mode passes
    match_partition_rules(llama_sharding_rules(fsdp=False), params)


def test_match_partition_rules_covers_mixtral():
    from ray_tpu.mesh.sharding import match_partition_rules
    from ray_tpu.models.mixtral import (Mixtral, mixtral_tiny,
                                        mixtral_sharding_rules)
    cfg = mixtral_tiny()
    params = jax.jit(Mixtral(cfg).init)(jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8), jnp.int32))
    match_partition_rules(mixtral_sharding_rules(fsdp=False), params)


def test_paged_append_typed_shape_errors():
    from ray_tpu.ops.paged_attention import (PagedShapeError,
                                             paged_append)
    KH, n_pages, Pg, D = 2, 8, 4, 8
    pk = jnp.zeros((n_pages, Pg, KH, D))
    pv = jnp.zeros((n_pages, Pg, KH, D))
    pt = jnp.zeros((2, 4), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    ok_k = jnp.zeros((2, 3, KH, D))
    # control: valid shapes pass
    paged_append(pk, pv, pt, pos, ok_k, ok_k)
    with pytest.raises(PagedShapeError, match="kv heads"):
        paged_append(pk, pv, pt, pos,
                     jnp.zeros((2, 3, KH + 2, D)),
                     jnp.zeros((2, 3, KH + 2, D)))
    with pytest.raises(PagedShapeError, match="head_dim"):
        paged_append(pk, pv, pt, pos,
                     jnp.zeros((2, 3, KH, D * 2)),
                     jnp.zeros((2, 3, KH, D * 2)))
    with pytest.raises(PagedShapeError, match="rank-4"):
        paged_append(pk, pv, pt, pos, jnp.zeros((2, 3, KH)),
                     jnp.zeros((2, 3, KH)))
    with pytest.raises(PagedShapeError, match="disagree"):
        paged_append(pk, pv, pt, pos, ok_k,
                     jnp.zeros((2, 3, KH, D + 1)))
    with pytest.raises(PagedShapeError, match="rows"):
        paged_append(pk, pv, jnp.zeros((5, 4), jnp.int32), pos,
                     ok_k, ok_k)
    with pytest.raises(PagedShapeError, match="integer"):
        paged_append(pk, pv, jnp.zeros((2, 4), jnp.float32), pos,
                     ok_k, ok_k)
    with pytest.raises(PagedShapeError, match="pos"):
        paged_append(pk, pv, pt, jnp.zeros((3,), jnp.int32),
                     ok_k, ok_k)
    # the checks fire at TRACE time (inside jit), not just eagerly
    with pytest.raises(PagedShapeError, match="kv heads"):
        jax.jit(paged_append)(pk, pv, pt, pos,
                              jnp.zeros((2, 3, KH * 2, D)),
                              jnp.zeros((2, 3, KH * 2, D)))


def test_deployment_tensor_parallel_knob(cpu_mesh_devices):
    """LlamaDeployment(tensor_parallel=4): the lazy engine comes up
    sharded; generation matches the tp=1 deployment token-for-token.
    Also: a non-dividing config fails at CONSTRUCTION."""
    from ray_tpu.serve.llm import LlamaDeployment
    cfg = llama_tiny(n_kv_heads=4, dtype=jnp.float32)
    prompt = list(range(1, 11))

    dep1 = LlamaDeployment(config=cfg, max_new_tokens=12,
                           max_slots=2, page_size=8)
    dep4 = LlamaDeployment(config=cfg, max_new_tokens=12,
                           max_slots=2, page_size=8,
                           tensor_parallel=4)
    try:
        assert dep1(prompt) == dep4(prompt)
        assert dep4.engine().load_report()["tp"] == 4
    finally:
        dep1.engine().shutdown()
        dep4.engine().shutdown()

    with pytest.raises(ShardingConfigError, match="n_kv_heads"):
        LlamaDeployment(config=llama_tiny(), tensor_parallel=4)


@pytest.mark.slow
def test_pool_of_sharded_replicas(cpu_mesh_devices):
    """2-D scale-out: num_engine_replicas=2 x tensor_parallel=2 on
    the 8-device host mesh — pool routing, per-replica load_report,
    and the aggregate tp stamp all compose unchanged."""
    from ray_tpu.serve.llm import LlamaDeployment
    cfg = llama_tiny(n_kv_heads=4, dtype=jnp.float32)
    prompt = list(range(1, 11))
    dep = LlamaDeployment(config=cfg, max_new_tokens=12,
                          max_slots=2, page_size=8,
                          num_engine_replicas=2, tensor_parallel=2)
    ref = LlamaDeployment(config=cfg, max_new_tokens=12,
                          max_slots=2, page_size=8)
    try:
        assert dep(prompt) == ref(prompt)
        rpt = dep.engine().load_report()
        assert rpt["tp"] == 2
        assert rpt["n_replicas"] == 2
    finally:
        dep.engine().shutdown()
        ref.engine().shutdown()
