"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, on one TPU chip, in one process,
through the entry points a user calls:

  serving   ray_tpu.init() -> serve.run() of a deployment wrapping
            LlamaDeployment (continuous-batching engine) at the
            TinyLlama-1.1B shape, all 22 layers, bf16, random weights
            from a seed; concurrent, streamed and HTTP requests; greedy
            output checked against the model's cache-free full forward;
            one int8-KV request checked the same way
  kernels   the Pallas flash-attention kernel (fwd+bwd), the one-token
            delta-rule kernel, the latent-page prefill attention kernel
            and the mixture's grouped matmul (the repo's own Pallas
            body, the matrix fetched by group) under its tile plan,
            compiled, each against its XLA reference
  training  GPT-2-124M at batch 24 x 1024 through shard_state /
            put_batch / make_train_step; flash kernel present in the
            compiled step; loss finite and falling

With ``--chips 4`` it runs ONLY the four-chip phase and what that is
compared with: four one-chip engine replicas on four distinct devices,
one tensor_parallel=4 engine, and the train step on a {"data": 4}
mesh, each against its one-chip twin. The engines there serve heads of
128, so that a one-chip replica's decode step is the Pallas kernel of
ops/paged_decode_attention.py and the tensor-parallel replica's is the
block loop.

There is no CPU branch: without a TPU the script exits non-zero at the
device check. A failed phase raises, and the script exits non-zero
without a result line. The last line of standard output is one JSON
object, {"ok": true, "device": {...}}; everything else worth reading
is printed before it. tests/test_chip_smoke.py rehearses the phase
functions at toy size on the CPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

SEED = 0
# Greedy parity rule. The engine's token at a step must have a
# reference logit within LOGIT_TOL of the reference's best; at every
# step where the reference's top-2 margin exceeds LOGIT_TOL that means
# "the same argmax token". bf16 carries 8 mantissa bits and the two
# programs reduce in different orders over 22 layers: 2**-5 (eight
# bf16 ulps) of the largest reference logit is the stated tolerance.
LOGIT_TOL_FRACTION = 2.0 ** -5
# int8 KV: the teacher-forced agreement floor tests/test_kv_quant.py
# holds the tiny model to
INT8_AGREE_FLOOR = 0.8
# relative L2 error allowed between a compiled kernel and its XLA
# reference on bf16 inputs (outputs round to bf16: 2**-8 per element)
KERNEL_REL_TOL = 2e-2
# float32 against float32, the order of one 128-term sum apart
KDA_REL_TOL = 1e-5
# one chip vs four chips, same global batch: per-step loss may differ
# by reduction order only
MULTICHIP_LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# What JAX itself reports about compilation (jax.monitoring events),
# summed since start: seconds tracing, lowering, in the backend
# compiler and reading the persistent cache, and cache hits/misses.
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_meter = dict.fromkeys(_JAX_EVENTS.values(), 0.0)


def _meter_compiles() -> None:
    from jax import monitoring

    def on_duration(event, seconds, **_):
        if event in _JAX_EVENTS:
            _meter[_JAX_EVENTS[event]] += seconds

    def on_event(event, **_):
        if event in _JAX_EVENTS:
            _meter[_JAX_EVENTS[event]] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


class timed:
    """``with timed("phase") as t`` prints and keeps the seconds, and
    what part of them JAX spent compiling (when main() meters it)."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.m0 = dict(_meter)
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        d = {k: _meter[k] - v for k, v in self.m0.items()}
        if exc[0] is None:
            jit = (f" (trace {d['trace_s']:.1f} lower {d['lower_s']:.1f}"
                   f" backend-compile {d['compile_s']:.1f} cache-read "
                   f"{d['cache_read_s']:.1f}s; cache hits "
                   f"{d['cache_hits']:.0f} misses "
                   f"{d['cache_misses']:.0f})"
                   if any(d.values()) else "")
            log(f"[{self.name}] {self.seconds:.1f}s{jit}")


# ------------------------------------------------------------ configs

def llama_1b_config(max_seq_len: int = 1024, n_heads: int = 32):
    """TinyLlama-1.1B (bench.py's llama-1.1b shape), bf16 weights.
    ``n_heads`` 16: the same width in heads of 128, which a decode
    step's Pallas kernel serves (ops/paged_decode_attention.py) where
    heads of 64 keep the block loop."""
    import jax.numpy as jnp
    from ray_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab_size=32000, max_seq_len=max_seq_len,
                       dim=2048, n_layers=22, n_heads=n_heads, n_kv_heads=4,
                       hidden_dim=5632, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)


def init_llama(cfg, seed: int = SEED):
    """The model and random weights of its own shapes and dtypes,
    from a seed. Filled leaf by leaf — one small program per distinct
    shape — with the model's own scales (1/sqrt(fan_in) kernels, 0.02
    embeddings, unit norms), because ``jit(model.init)`` as ONE
    program took the chip's compiler 62 s at 22 layers (my chip run,
    PR 23)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.llama import Llama
    model = Llama(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    def fill(i, path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 1:                          # RMSNorm scales
            return jnp.ones(leaf.shape, leaf.dtype)
        std = 0.02 if "tok_embeddings" in name else leaf.shape[0] ** -0.5
        return normal(jax.random.fold_in(jax.random.PRNGKey(seed), i),
                      leaf.shape, leaf.dtype, std)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    with timed("init params"):
        params = jax.block_until_ready(jax.tree_util.tree_unflatten(
            treedef, [fill(i, p, l) for i, (p, l) in enumerate(leaves)]))
    return model, params


def make_prompts(cfg, n: int, length: int, seed: int = SEED):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size - 1, size=length).tolist()
            for _ in range(n)]


# ---------------------------------------------------------- reference

@functools.lru_cache(maxsize=None)
def _full_forward(model):
    """One jitted ``model.apply`` per model: the bf16 and int8 checks
    share its compiled program."""
    import jax
    return jax.jit(model.apply)


def reference_deficits(model, params, prompts, generated):
    """Teacher-forced parity against the plain full forward (no cache,
    no engine): run ``prompt + generated`` through ``model.apply`` and,
    for every generated token, report how far its reference logit lies
    below the reference's best at that step (0 = same argmax), plus
    the reference's top-2 margin and largest |logit|. Rows must share
    one prompt length and one generation length."""
    import jax.numpy as jnp
    import numpy as np
    ids = np.asarray([p + g for p, g in zip(prompts, generated)],
                     np.int32)
    P, G = len(prompts[0]), len(generated[0])
    logits, _ = _full_forward(model)(params, jnp.asarray(ids))
    # logits at position t predict token t+1
    steps = np.asarray(logits[:, P - 1:P - 1 + G].astype(jnp.float32))
    assert np.isfinite(steps).all(), "reference logits not finite"
    top2 = np.sort(steps, axis=-1)[..., -2:]
    best, margin = top2[..., 1], top2[..., 1] - top2[..., 0]
    chosen = np.take_along_axis(
        steps, ids[:, P:P + G, None].astype(np.int64), axis=-1)[..., 0]
    return best - chosen, margin, float(np.abs(steps).max())


def check_greedy_parity(name, model, params, prompts, generated):
    """The bf16 rule of the module header; raises on a violation."""
    deficit, margin, scale = reference_deficits(model, params, prompts,
                                                generated)
    tol = LOGIT_TOL_FRACTION * scale
    decisive = margin > tol
    log(f"[{name}] parity vs full forward: {deficit.size} steps, "
        f"{int(decisive.sum())} with top-2 margin > tol={tol:.4f} "
        f"(max|logit| {scale:.2f}); same argmax at "
        f"{int((deficit == 0).sum())}; worst deficit "
        f"{float(deficit.max()):.4f}")
    assert decisive.any(), f"{name}: no step had a decisive margin"
    assert (deficit <= tol).all(), (
        f"{name}: engine token {float(deficit.max()):.4f} below the "
        f"reference's best logit, over tol {tol:.4f}")
    return {"steps": int(deficit.size),
            "decisive": int(decisive.sum()),
            "worst_deficit": float(deficit.max()), "tol": tol}


def check_int8_parity(name, model, params, prompts, generated,
                      rows: int):
    """Teacher-forced argmax agreement of the first ``rows`` rows (the
    int8-KV output; the rest only pad the batch to the shape the bf16
    check already compiled)."""
    deficit, _margin, _scale = reference_deficits(model, params,
                                                  prompts, generated)
    deficit = deficit[:rows]
    agree = float((deficit == 0).mean())
    log(f"[{name}] int8 KV vs full forward: argmax agreement "
        f"{agree:.3f} over {deficit.size} steps (floor "
        f"{INT8_AGREE_FLOOR}); worst deficit "
        f"{float(deficit.max()):.4f}")
    assert agree >= INT8_AGREE_FLOOR, (
        f"{name}: int8 agreement {agree:.3f} < {INT8_AGREE_FLOOR}")
    return {"agree": agree}


# ------------------------------------------------------------ serving

def serving_phase(cfg, *, n_requests: int = 8, prompt_len: int = 128,
                  new_tokens: int = 32, max_slots: int = 16,
                  page_size: int = 64, seed: int = SEED) -> dict:
    """serve.run -> LlamaDeployment -> engine, as a user deploys it.
    Needs an initialised ray_tpu runtime; leaves serve shut down."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_http, stop_http
    from ray_tpu.serve.llm import LlamaDeployment

    model, params = init_llama(cfg, seed)
    prompts = make_prompts(cfg, n_requests, prompt_len, seed)

    @serve.deployment(max_ongoing_requests=32)
    class Llm:
        def __init__(self, kv_dtype=None):
            self.inner = LlamaDeployment(
                config=cfg, params=params, max_new_tokens=new_tokens,
                max_slots=max_slots, page_size=page_size,
                kv_dtype=kv_dtype)

        def __call__(self, prompt_ids):
            return self.inner(prompt_ids)

        def stream(self, prompt_ids):
            yield from self.inner.stream(prompt_ids)

    def wave(handle, batch):
        outs = [None] * len(batch)

        def client(i):
            outs[i] = ray_tpu.get(handle.remote(batch[i]), timeout=900)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(batch))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        gen = []
        for p, o in zip(batch, outs):
            assert o is not None, "a request returned nothing"
            assert o[:len(p)] == p, "response does not echo its prompt"
            assert len(o) == len(p) + new_tokens, (
                f"asked {new_tokens} tokens, got {len(o) - len(p)}")
            gen.append(o[len(p):])
        return gen

    try:
        handle = serve.run(Llm.bind(), timeout_s=900)
        with timed(f"serving: {n_requests} concurrent requests, cold "
                   f"(compiles included)") as t_cold:
            gen = wave(handle, prompts)
        with timed(f"serving: {n_requests} concurrent requests, "
                   f"warm") as t_warm:
            gen2 = wave(handle, prompts)
        assert gen2 == gen, "greedy output changed between two waves"
        log(f"[serving] {n_requests * new_tokens} tokens in "
            f"{t_warm.seconds:.2f}s warm = "
            f"{n_requests * new_tokens / t_warm.seconds:.1f} tok/s")

        streamed = list(handle.stream.options(stream=True).remote(
            prompts[0]))
        assert streamed == gen[0], "streamed tokens differ from unary"

        proxy = start_http(port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{proxy.port}/Llm", method="POST",
                data=json.dumps(prompts[1]).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=900) as resp:
                body = json.loads(resp.read())
        finally:
            stop_http()
        assert body["result"] == prompts[1] + gen[1], \
            "HTTP response differs from the handle's"
        log(f"[serving] streamed {len(streamed)} tokens; HTTP proxy "
            f"returned {len(body['result']) - prompt_len}")

        parity = check_greedy_parity("serving", model, params, prompts,
                                     gen)

        h8 = serve.run(Llm.options(name="LlmInt8").bind("int8"),
                       timeout_s=900)
        with timed("serving: one int8-KV request (compiles included)"):
            gen8 = wave(h8, prompts[:1])
        parity8 = check_int8_parity("serving", model, params, prompts,
                                    gen8 + gen[1:], rows=1)
    finally:
        serve.shutdown()
    return {"cold_s": t_cold.seconds,
            "warm_s": t_warm.seconds, "parity": parity,
            "int8": parity8, "tokens": n_requests * new_tokens}


# ------------------------------------------------------------ kernels

def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert np.isfinite(a).all(), "kernel output not finite"
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def kernel_phase(*, flash_shapes=((24, 1024, 12, 64),
                                  (8, 1024, 32, 64)),
                 kda_shapes=((32, 64, 128),),
                 window_shapes=((256, 64, 640, 512,
                                 (512, 8192, None, 2304), None),),
                 gmm_shapes=((64, 2304, 1024, 256, 200),
                             (64, 2304, 896, 8192, 8192)),
                 decode_shapes=((16, 16, (300, 352, None, 64, 1)),
                                (32, 4, (8704, None, 70)),
                                (64, None, (8704, None, 8200, 70)),
                                (32, None, (2048, 1030, None, 1024))),
                 interpret: bool = False, seed: int = SEED) -> dict:
    """The flash-attention kernel, compiled (not interpreted, unless
    the CPU rehearsal asks) and compared with its XLA reference,
    forward and backward; the one-token delta-rule kernel at a serving
    cell's state (slots, heads, head width) against the ``jax.numpy``
    form, two chained steps with a row that starts a request and a row
    that rides nothing; a prefill chunk's attention over latent pages
    (``window_shapes``: chunk, heads, entry and value widths, where
    each row's window ends, None a row no request owns, and a query
    tile's tokens where not the kernel's own) at a serving cell's shape
    against the block loop; a decode step's attention over K/V pages
    (``decode_shapes``: heads, KV heads, each row's context, None a row
    no request owns; KV heads None: over latent pages as
    ``window_shapes``' first, A.X-K1's 64 heads and Kimi-Linear's 32 at
    their cells' contexts) against the block loop too; the mixture's grouped
    matmul (``ops/grouped_matmul.py`` ``grouped_matmul_kernel``: the
    repo's own body, which starts the next group's matrix on its way at
    a group's first visit; ``gmm_shapes``: experts, K, N, sorted pairs,
    pairs that have an expert) at a decode call's few rows a group and
    at a prefill call's ~128, where a group lies over two row tiles and
    the second visit multiplies out of the matrix already there, against
    ``jax.lax.ragged_dot`` in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import flash_attention as flash_mod
    from ray_tpu.ops.attention import xla_attention
    assert flash_mod._interpret() == interpret, (
        "flash_attention would run "
        + ("interpreted" if flash_mod._interpret() else "compiled"))
    rng = np.random.default_rng(seed)
    errs = {}

    for Bq, T, H, D in flash_shapes:
        name = f"flash_B{Bq}_T{T}_H{H}_D{D}"
        q, k, v = (jnp.asarray(rng.standard_normal((Bq, T, H, D)),
                               jnp.bfloat16) for _ in range(3))
        w = jnp.asarray(rng.standard_normal((Bq, T, H, D)),
                        jnp.bfloat16)

        def run(attn):
            # w rides as an ARGUMENT: closed over, a 38 MB array is
            # baked into the executable as a constant (a 133.6 MiB
            # compile-cache entry; my chip run, PR 23)
            def loss(q, k, v, w):
                o = attn(q, k, v, causal=True)
                return (o.astype(jnp.float32)
                        * w.astype(jnp.float32)).sum(), o

            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)

        with timed(f"kernels: {name} fwd+bwd"):
            (_, o), g = run(flash_mod.flash_attention)
            (_, o_ref), g_ref = run(
                lambda q, k, v, causal: xla_attention(
                    q, k, v, causal=causal, precision="highest"))
            errs[name + "_fwd"] = _rel_err(o, o_ref)
            for n, a, b in zip("qkv", g, g_ref):
                errs[f"{name}_d{n}"] = _rel_err(a, b)

    from ray_tpu.ops import linear_attention as la
    for slots, H, d in kda_shapes:
        name = f"kda_step_B{slots}_H{H}_D{d}"
        q, k, v, g = (jnp.asarray(rng.standard_normal((2, slots, H, d)),
                                  jnp.float32) for _ in range(4))
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        q, g = q * d ** -1.0, -jnp.exp(g)
        beta = jnp.asarray(rng.uniform(0.0, 2.0, (2, slots, H)),
                           jnp.float32)
        state = jnp.asarray(rng.standard_normal((slots, H, d, d)),
                            jnp.float32)
        valid = jnp.arange(slots) != 1
        fresh = jnp.arange(slots) == 2

        def two(step):
            def run(q, k, v, g, beta, s, valid, fresh):
                o, s = step(q[0], k[0], v[0], g[0], beta[0], s, valid,
                            fresh)
                return (o,) + tuple(step(q[1], k[1], v[1], g[1],
                                         beta[1], s, valid, None))
            return jax.jit(run)(q, k, v, g, beta, state, valid, fresh)

        with timed(f"kernels: {name}"):
            got = two(functools.partial(la.kda_step_kernel,
                                        interpret=interpret))
            want = two(la._kda_step_xla)
            rows = np.asarray(valid)
            for n, a, b in zip(("o1", "o2", "state"), got, want):
                rides = rows if n != "state" else slice(None)
                errs[f"{name}_{n}"] = _rel_err(a[rides], b[rides])
            assert (np.asarray(got[2])[1] == np.asarray(state)[1]).all(), (
                f"{name}: the row that rides nothing moved its state")

    from unittest import mock

    from ray_tpu.ops import latent_window_attention as lw
    from ray_tpu.ops import paged_attention as pa
    page, max_pages = 64, 256

    def rows_of(ends, T):
        """(page table, first query's position, live rows) of rows whose
        ``T`` queries end at ``ends`` (None: a row no request owns, its
        table row null and its position stale), their pages scattered."""
        B = len(ends)
        ids = 1 + rng.permutation(B * max_pages).reshape(B, max_pages)
        table = np.zeros((B, max_pages), np.int32)
        pos = np.full((B,), 10 ** 6, np.int32)
        for b, end in enumerate(ends):
            if end is not None:
                pos[b] = end - T
                table[b, :-(-end // page)] = ids[b, :-(-end // page)]
        return table, pos, [b for b, end in enumerate(ends)
                            if end is not None]

    for T, H, D, Dv, ends, tokens in window_shapes:
        name = f"latent_window_T{T}_H{H}_D{D}"
        B = len(ends)
        dtype = jnp.float32 if interpret else jnp.bfloat16
        table, pos, live = rows_of(ends, T)
        pages = jnp.asarray(
            rng.standard_normal((1 + B * max_pages, page, D)), dtype)
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
        with timed(f"kernels: {name}"):
            got = jax.jit(functools.partial(
                lw.latent_window_attention, softmax_scale=D ** -0.5,
                value_dim=Dv, tokens=tokens, interpret=interpret,
                block_pages=pa.paged_window_block_pages(page, max_pages))
            )(q, pages, table, pos)
            with mock.patch.object(lw, "_on_one_tpu", lambda: False):
                want = jax.jit(functools.partial(
                    pa._paged_window_attention, softmax_scale=D ** -0.5,
                    value_dim=Dv))(q, pages, None, None, None, table, pos)
            errs[name] = _rel_err(np.asarray(got, np.float32)[live],
                                  np.asarray(want, np.float32)[live])

    from ray_tpu.ops import paged_decode_attention as pd
    _T, _H, latent_d, latent_dv = window_shapes[0][:4]
    for H, KH, contexts in decode_shapes:
        name = f"paged_decode_H{H}_KH{KH or 'latent'}"
        B = len(contexts)
        dtype = jnp.float32 if interpret else jnp.bfloat16
        table, pos, live = rows_of(contexts, 1)
        if KH is None:
            # an absorbed query is as wide as a stored entry; the scale
            # is not the width's (A.X-K1's under YaRN)
            D, pools = latent_d, (jnp.asarray(rng.standard_normal(
                (1 + B * max_pages, page, latent_d)), dtype), None)
            how = dict(softmax_scale=0.1309, value_dim=latent_dv)
        else:
            D, how = 128, dict(softmax_scale=128 ** -0.5)
            pools = tuple(jnp.asarray(rng.standard_normal(
                (1 + B * max_pages, page, KH, D)), dtype) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
        with timed(f"kernels: {name}"):
            got = pd.paged_decode_attention(
                q, *pools, table, pos, interpret=interpret, **how)
            with mock.patch.object(pd, "_on_one_tpu", lambda: False):
                want = jax.jit(functools.partial(
                    pa._paged_window_attention, **(
                        how if KH is None else {})))(
                    q, *pools, None, None, table, pos)
            errs[name] = _rel_err(np.asarray(got, np.float32)[live],
                                  np.asarray(want, np.float32)[live])

    from ray_tpu.ops import grouped_matmul as gm
    for E, K, N, M, held in gmm_shapes:
        name = f"grouped_matmul_E{E}_K{K}_N{N}_M{M}"
        dtype = jnp.float32 if interpret else jnp.bfloat16
        rows = jnp.asarray(rng.standard_normal((M, K)), dtype)
        w = jnp.asarray(rng.standard_normal((E, K, N)) * K ** -0.5, dtype)
        sizes = jnp.asarray(rng.multinomial(held, np.full(E, 1.0 / E)),
                            jnp.int32)
        with timed(f"kernels: {name}"):
            got = jax.jit(functools.partial(
                gm.grouped_matmul_kernel, interpret=interpret))(
                    rows, w, sizes)
            want = jax.jit(functools.partial(
                jax.lax.ragged_dot, precision="highest"))(
                    rows.astype(jnp.float32), w.astype(jnp.float32), sizes)
            errs[name] = _rel_err(got[:held], want[:held])

    for name, e in errs.items():
        log(f"[kernels] {name}: rel err {e:.2e}")
    bad = {n: e for n, e in errs.items()
           if not e <= (KDA_REL_TOL if n.startswith("kda") else
                        KERNEL_REL_TOL)}
    assert not bad, f"kernels beyond their rel tol: {bad}"
    return errs


# ----------------------------------------------------------- training

def training_phase(cfg=None, *, batch: int = 24, seq: int = 1024,
                   steps: int = 4, expect_flash: bool = True,
                   devices=None, seed: int = SEED) -> dict:
    """The SPMD train step exactly as bench.py and
    examples/02_train_gpt2.py build it, on a {"data": -1} mesh over
    ``devices`` (default: all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from ray_tpu.mesh import create_mesh
    from ray_tpu.models import GPT2, gpt2_124m, gpt2_sharding_rules
    from ray_tpu.models.gpt2 import linear_cross_entropy
    from ray_tpu.train.spmd import (TrainState, make_train_step,
                                    put_batch, shard_state)
    cfg = cfg or gpt2_124m()
    model = GPT2(cfg)
    mesh = create_mesh({"data": -1}, devices=devices)
    n_dev = mesh.devices.size
    tag = f"training[{n_dev} chip]"

    def loss_fn(params, b):
        x, y = b["ids"][:, :-1], b["ids"][:, 1:]
        feats = model.apply(params, x, return_features=True)
        return linear_cross_entropy(feats, params["params"]["wte"], y)

    ids = jnp.zeros((batch, seq + 1), jnp.int32)
    with jax.default_device(mesh.devices.flat[0]):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                     ids[:, :-1])
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    state = shard_state(TrainState.create(params, optimizer),
                        gpt2_sharding_rules(fsdp=False), mesh)
    train_step = make_train_step(loss_fn, optimizer)
    data = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)
    losses = []
    with jax.set_mesh(mesh):
        b = put_batch({"ids": jnp.asarray(data)}, mesh)
        with timed(f"{tag}: compile") as t_compile:
            compiled = train_step.lower(state, b).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        assert has_kernel == expect_flash, (
            f"{tag}: flash kernel "
            f"{'missing from' if expect_flash else 'unexpected in'} "
            f"the compiled step — attention_impl='auto' picked "
            f"{'XLA' if expect_flash else 'Pallas'}")
        with timed(f"{tag}: {steps} steps of {batch} x {seq}") as t_run:
            for _ in range(steps):
                state, metrics = compiled(state, b)
                losses.append(float(metrics["loss"]))
    log(f"[{tag}] losses {[round(x, 4) for x in losses]}; "
        f"{batch * seq * steps / t_run.seconds:.0f} tok/s incl. first "
        f"dispatch; flash kernel in step: {has_kernel}")
    assert all(np.isfinite(losses)), f"{tag}: loss not finite: {losses}"
    assert losses[-1] < losses[0], f"{tag}: loss did not fall: {losses}"
    return {"losses": losses, "compile_s": t_compile.seconds,
            "run_s": t_run.seconds}


# --------------------------------------------------------- four chips

def _engine_outputs(dep, prompts, new_tokens):
    hs = [dep.engine().submit(p, max_new_tokens=new_tokens)
          for p in prompts]
    return [h.result() for h in hs]


def multichip_phase(cfg, gpt2_cfg=None, *, n_chips: int = 4,
                    n_prompts: int = 4, prompt_len: int = 128,
                    new_tokens: int = 32, max_slots: int = 16,
                    page_size: int = 64, train_batch: int = 24,
                    train_seq: int = 1024, train_steps: int = 4,
                    expect_flash: bool = True,
                    seed: int = SEED) -> dict:
    """What exists only across chips, each against its one-chip twin:
    (a) n one-chip replicas behind the pool, each on its own device;
    (b) one tensor-parallel engine over all n; (c) the data-parallel
    train step."""
    import jax
    import numpy as np
    from ray_tpu.serve.llm import LlamaDeployment
    devs = jax.devices()[:n_chips]
    assert len(devs) == n_chips, f"need {n_chips} devices, have {devs}"
    model, params = init_llama(cfg, seed)
    prompts = make_prompts(cfg, n_prompts, prompt_len, seed)
    kw = dict(config=cfg, params=params, max_new_tokens=new_tokens,
              max_slots=max_slots, page_size=page_size)

    def leaf_devices(tree):
        return jax.tree_util.tree_leaves(tree)[0].devices()

    with timed("4chip: one-chip engine (the twin)"):
        one = LlamaDeployment(**kw)
        want = _engine_outputs(one, prompts, new_tokens)
        one_pages = one.engine().stats["decode_kernel_pages"]
        one.engine().shutdown()
    assert all(len(g) == new_tokens for g in want)

    with timed(f"4chip: {n_chips} one-chip replicas"):
        pooled = LlamaDeployment(num_engine_replicas=n_chips, **kw)
        pool = pooled.engine()
        try:
            placed = []
            for i, eng in enumerate(pool.engines()):
                p_dev, kv_dev = (leaf_devices(eng.params),
                                 leaf_devices(eng.pages))
                assert p_dev == kv_dev and len(p_dev) == 1, (
                    f"replica {i}: params on {p_dev}, pool on {kv_dev}")
                placed.append(next(iter(p_dev)))
                # every replica answers every prompt, token for token
                hs = [eng.submit(p, max_new_tokens=new_tokens)
                      for p in prompts]
                got = [h.result() for h in hs]
                assert got == want, (
                    f"replica {i} on {placed[-1]} differs from the "
                    f"one-chip engine")
            assert len(set(placed)) == n_chips, (
                f"replicas share devices: {placed}")
            # and through the pool's own front door
            hs = [pool.submit(p, max_new_tokens=new_tokens)
                  for p in prompts]
            assert [h.result() for h in hs] == want
        finally:
            pool.shutdown()
    log(f"[4chip] replicas on {[str(d) for d in placed]}, each "
        f"token-identical to the one-chip engine over "
        f"{n_prompts} prompts")

    with timed(f"4chip: tensor_parallel={n_chips} engine"):
        tp = LlamaDeployment(tensor_parallel=n_chips, **kw)
        eng = tp.engine()
        try:
            got = _engine_outputs(tp, prompts, new_tokens)
            p_dev, kv_dev = (leaf_devices(eng.params),
                             leaf_devices(eng.pages))
            assert len(p_dev) == n_chips and len(kv_dev) == n_chips, (
                f"tp engine: params on {p_dev}, pool on {kv_dev}")
            shard = eng.pages[0][0].addressable_shards[0].data.shape
            # page-major pool [n_pages, Pg, KH, D]: the head axis is 2
            assert shard[2] * n_chips == cfg.n_kv_heads, (
                f"KV pool not head-sharded: shard {shard}")
            # GSPMD cannot partition a Mosaic kernel: a sharded
            # replica's decode step is the block loop whatever its
            # shapes (serve/step_programs.py ambient_mesh)
            tp_pages = eng.stats["decode_kernel_pages"]
            assert tp_pages == 0, tp_pages
        finally:
            eng.shutdown()
    same = sum(a == b for a, b in zip(got, want))
    log(f"[4chip] tp={n_chips}: pool shard {shard} per chip; "
        f"{same}/{n_prompts} prompts token-identical to one chip; "
        f"decode_kernel_pages {one_pages} on one chip, {tp_pages} "
        f"under tp")
    parity = check_greedy_parity(f"4chip tp={n_chips}", model, params,
                                 prompts, got)
    del params, one, pooled, tp, pool, eng

    t1 = training_phase(gpt2_cfg, batch=train_batch, seq=train_seq,
                        steps=train_steps, expect_flash=expect_flash,
                        devices=devs[:1], seed=seed)
    tn = training_phase(gpt2_cfg, batch=train_batch, seq=train_seq,
                        steps=train_steps, expect_flash=expect_flash,
                        devices=devs, seed=seed)
    np.testing.assert_allclose(
        tn["losses"], t1["losses"], rtol=MULTICHIP_LOSS_RTOL,
        err_msg=f"{n_chips}-chip losses differ from one chip's")
    log(f"[4chip] train losses 1 chip {t1['losses']} vs {n_chips} "
        f"chips {tn['losses']} (rtol {MULTICHIP_LOSS_RTOL})")
    return {"placed": [str(d) for d in placed], "tp_parity": parity,
            "one_chip_kernel_pages": one_pages,
            "losses_1": t1["losses"], "losses_n": tn["losses"]}


# --------------------------------------------------------------- main

def _cache_report(path: str, top: int = 0) -> str:
    """Entries (executables, not their bookkeeping files) and bytes in
    the compile cache, the size JAX may evict down to, and the largest
    entries by name — a cache smaller than the run's working set
    evicts in a cycle and never hits."""
    import jax
    sizes = {}
    if os.path.isdir(path):
        for name in os.listdir(path):
            if name.endswith("-cache"):
                sizes[name] = os.path.getsize(os.path.join(path, name))
    cap = jax.config.jax_compilation_cache_max_size
    out = (f"{len(sizes)} entries, {sum(sizes.values()) / 2**20:.1f} "
           f"MiB (max size "
           f"{'unlimited' if cap < 0 else f'{cap / 2**20:.0f} MiB'})")
    for name in sorted(sizes, key=sizes.get, reverse=True)[:top]:
        out += f"\n[cache]   {sizes[name] / 2**20:7.1f} MiB  {name[:60]}"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    log(f"[device] {device}")

    import ray_tpu
    from ray_tpu.util.compile_cache import enable_compile_cache
    _meter_compiles()
    cache_dir = enable_compile_cache()
    log(f"[cache] {cache_dir} before: {_cache_report(cache_dir)}")

    ray_tpu.init()
    try:
        tpus = ray_tpu.cluster_resources().get("TPU")
        assert tpus == float(len(devs)), (
            f"ray_tpu.init() reports TPU={tpus}, JAX has {len(devs)}")
        if args.chips == 4:
            with timed("4chip phase"):
                # heads of 128: the one-chip engines decode through
                # the Pallas kernel, the tensor-parallel one through
                # the loop, and their tokens are compared
                out = multichip_phase(llama_1b_config(n_heads=16))
                assert out["one_chip_kernel_pages"] > 0, out
        else:
            with timed("serving phase"):
                serving_phase(llama_1b_config())
            with timed("kernel phase"):
                kernel_phase()
            with timed("training phase"):
                training_phase()
    finally:
        ray_tpu.shutdown()
    stats = devs[0].memory_stats() or {}
    log(f"[hbm] peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} "
        f"GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB on "
        f"{devs[0]}")
    log(f"[cache] {cache_dir} after: {_cache_report(cache_dir, top=6)}")
    log(f"[total] {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine, serve and proxy threads must not keep a failed (or a
    # finished) run alive on the chip
    os._exit(rc)
