"""ops/linear_attention.py: the gated delta rule's chunked form (a
prefill row) and its one-token form (a decode step) against each other
and against the plain reference's token-by-token scan
(benchmarks/reference/solar_open2.py ``delta_rule_scan``), float32 on
the CPU.

The one-token form is a Pallas kernel on a TPU: here it runs in
interpret mode against the ``jax.numpy`` form the CPU serves, at the
serving cells' head counts and width (tests/test_chip_compile.py
compiles it for the chip; chip_smoke.py runs it there).

Tolerance. All three compute the same recurrence in float32 and differ
in the order of their sums (the chunked form solves a chunk's writes at
once through a triangular system); with l2-normed keys the state and the
outputs are of order 1 and agree to 1e-5 absolute, so rtol 1e-4 /
atol 2e-5 holds with room. A state handed over in bfloat16 between two
calls (8 mantissa bits) misses it by fifty times and more, and the
last test says so.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.solar_open2 import delta_rule_scan, l2norm
from ray_tpu.ops import linear_attention as la
from ray_tpu.ops.linear_attention import kda_chunked, kda_step

RTOL, ATOL = 1e-4, 2e-5
B, H, D = 2, 3, 16


def _inputs(T, seed=0, hard=True):
    """Unit keys, values of order 1, beta up to 2 and per-channel
    log-decays from -0.001 down to -20 a step where ``hard``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (B, T, H, D))) * D ** -0.5
    k = l2norm(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, D), minval=-7.0,
                                    maxval=3.0 if hard else -3.0))
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, D, D))
    return q, k, v, g, beta, state


def _scan(*args):
    with jax.default_matmul_precision("highest"):
        return delta_rule_scan(*args)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("T,chunk", [(150, 64), (64, 64), (40, 16),
                                     (7, 64), (129, 32)])
def test_chunked_matches_the_scan(T, chunk):
    """T not a multiple of the chunk, hard decays (g down to -20 a
    step: exp(+20 x 64) would overflow a factored form), beta to 2, a
    state that is not zero."""
    q, k, v, g, beta, state = _inputs(T)
    if T >= 40:
        assert float(g.min()) < -19.0 and float(beta.max()) > 1.99
    o, s = jax.jit(kda_chunked, static_argnames="chunk")(
        q, k, v, g, beta, state, chunk=chunk)
    want_o, want_s = _scan(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(o)).all()
    _close(o, want_o)
    _close(s, want_s)


def test_step_looped_matches_the_scan():
    T = 70
    q, k, v, g, beta, state = _inputs(T, seed=1)
    step = jax.jit(kda_step)
    outs, s = [], state
    for t in range(T):
        o, s = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o)
    want_o, want_s = _scan(q, k, v, g, beta, state)
    _close(jnp.stack(outs, axis=1), want_o)
    _close(s, want_s)


def test_padding_inside_a_row_moves_nothing():
    """Rows of 100 and 37 real positions in a call of 150: the outputs
    at the real positions and the state left behind are those of the
    rows alone; a step that is not valid leaves the state as it was."""
    T, real = 150, (100, 37)
    q, k, v, g, beta, state = _inputs(T, seed=2)
    valid = jnp.arange(T)[None] < jnp.asarray(real)[:, None]
    o, s = kda_chunked(q, k, v, g, beta, state, valid, chunk=64)
    for b, n in enumerate(real):
        row = tuple(a[b:b + 1, :n] for a in (q, k, v, g, beta))
        want_o, want_s = _scan(*row, state[b:b + 1])
        _close(o[b, :n], want_o[0])
        _close(s[b], want_s[0])
    o1, s1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                      state, jnp.asarray([True, False]))
    assert (np.asarray(s1[1]) == np.asarray(state[1])).all()
    assert not (np.asarray(s1[0]) == np.asarray(state[0])).all()


def test_two_calls_with_the_state_handed_over():
    """A row in two calls (two engine rounds), then decode steps: the
    state carries everything."""
    T, cut = 120, 72
    q, k, v, g, beta, state = _inputs(T + 3, seed=3)
    first = tuple(a[:, :cut] for a in (q, k, v, g, beta))
    second = tuple(a[:, cut:T] for a in (q, k, v, g, beta))
    o1, s1 = kda_chunked(*first, state, chunk=64)
    o2, s2 = kda_chunked(*second, s1, chunk=64)
    outs, s = [o1, o2], s2
    for t in range(T, T + 3):
        o, s = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o[:, None])
    want_o, want_s = _scan(q, k, v, g, beta, state)
    _close(jnp.concatenate(outs, axis=1), want_o)
    _close(s, want_s)


def test_a_bfloat16_state_would_fail():
    """The same two calls with the state rounded to bfloat16 between
    them: outside the tolerance by far (mild decays, so the first
    call's state still matters in the second)."""
    T, cut = 120, 72
    q, k, v, g, beta, state = _inputs(T, seed=3, hard=False)
    first = tuple(a[:, :cut] for a in (q, k, v, g, beta))
    second = tuple(a[:, cut:] for a in (q, k, v, g, beta))
    _, s1 = kda_chunked(*first, state, chunk=64)
    rounded = s1.astype(jnp.bfloat16).astype(jnp.float32)
    o2, _ = kda_chunked(*second, rounded, chunk=64)
    want_o, _ = _scan(q, k, v, g, beta, state)
    gap = np.abs(np.asarray(o2) - np.asarray(want_o[:, cut:])).max()
    assert gap > 50 * ATOL, gap


# ------------------------------------------------ the one-token kernel

def _step_inputs(n, H, d=128, T=1, seed=4):
    """T tokens for n slots of H heads of d: unit keys, beta up to 2
    (``kda_allow_neg_eigval``), log-decays down to -20 a step and one
    channel in eight at exactly -20, a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (n, T, H, d))) * d ** -0.5
    k = l2norm(jax.random.normal(ks[1], (n, T, H, d)))
    v = jax.random.normal(ks[2], (n, T, H, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, T, H, d), minval=-7.0,
                                    maxval=3.0))
    g = jnp.where(jnp.arange(d) % 8 == 0, -20.0, g)
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (n, T, H)))
    state = jax.random.normal(ks[5], (n, H, d, d))
    return (q, k, v, g, beta), state


_kernel = jax.jit(la.kda_step_kernel, static_argnames=("plan", "interpret"))


# (heads of the model, heads a block): Kimi-Linear's 32 and
# Solar-Open2's 64 under their own plan, under blocks that divide the
# heads and under blocks that do not (the last block hangs over)
@pytest.mark.parametrize("H,block", [(32, None), (64, None), (32, 16),
                                     (64, 16), (32, 24), (64, 24)])
def test_step_kernel_matches_the_step_and_the_scan(H, block):
    """Five slots: two plain, one that STARTS a request (from zeros,
    whatever the slot held), one that rides nothing (its state comes
    back bit for bit), one plain after it."""
    n = 5
    (q, k, v, g, beta), state = _step_inputs(n, H)
    assert float(beta.max()) > 1.99 and float(g.min()) <= -20.0
    state = state.at[2, 0].set(jnp.inf)     # what a slot held is unread
    valid = jnp.asarray([True, True, True, False, True])
    fresh = jnp.asarray([False, False, True, False, False])
    plan = block and la.StepPlan(block, 2)
    if plan is None:
        assert la.step_plan(H, 128, 128).heads == 32
    one = tuple(a[:, 0] for a in (q, k, v, g, beta))
    o, s = _kernel(*one, state, valid, fresh, plan=plan, interpret=True)
    want_o, want_s = la._kda_step_xla(*one, state, valid, fresh)
    rides = np.asarray(valid)
    _close(o[rides], want_o[rides])
    _close(s, want_s)
    assert (np.asarray(s[3]) == np.asarray(state[3])).all()
    assert not np.asarray(o[3]).any()
    began = jnp.where(fresh[:, None, None, None], 0.0, state)
    scan_o, scan_s = _scan(q, k, v, g, beta, began)
    _close(o[rides], scan_o[rides, 0])
    _close(s[rides], scan_s[rides])
    # the slot that started a request holds what zeros would have left
    zero_o, zero_s = _kernel(*(a[2:3] for a in one),
                             jnp.zeros_like(state[2:3]), plan=plan,
                             interpret=True)
    assert (np.asarray(s[2]) == np.asarray(zero_s[0])).all()
    assert (np.asarray(o[2]) == np.asarray(zero_o[0])).all()


@pytest.mark.parametrize("riding", [(), (1,), (0, 2), (2,)])
def test_step_kernel_visits_the_riding_rows_whichever_they_are(riding):
    """No row rides, one, the outer two, the last: the rows that ride
    move as the step moves them, the others keep every bit."""
    n, H = 3, 8
    (q, k, v, g, beta), state = _step_inputs(n, H, seed=5)
    valid = jnp.zeros((n,), bool).at[jnp.asarray(riding, int)].set(True)
    one = tuple(a[:, 0] for a in (q, k, v, g, beta))
    o, s = _kernel(*one, state, valid, None, interpret=True)
    want_o, want_s = la._kda_step_xla(*one, state, valid, None)
    for row in range(n):
        if row in riding:
            _close(o[row], want_o[row])
            _close(s[row], want_s[row])
        else:
            assert (np.asarray(s[row]) == np.asarray(state[row])).all()


def test_eight_kernel_steps_match_the_chunked_form():
    """A decode dispatch's 8 chained steps against the same 8 tokens as
    one prefill row, and against the scan."""
    T = 8
    (q, k, v, g, beta), state = _step_inputs(2, 8, T=T, seed=6)
    outs, s = [], state
    for t in range(T):
        o, s = _kernel(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s,
                       interpret=True)
        outs.append(o)
    o = jnp.stack(outs, axis=1)
    want_o, want_s = jax.jit(kda_chunked)(q, k, v, g, beta, state)
    _close(o, want_o)
    _close(s, want_s)
    scan_o, scan_s = _scan(q, k, v, g, beta, state)
    _close(o, scan_o)
    _close(s, scan_s)


def test_the_kernel_is_chosen_by_what_the_code_can_observe(monkeypatch):
    """On the CPU ``kda_step`` is the ``jax.numpy`` form; on one TPU it
    is the kernel where the heads tile, and only there."""
    tiles = jnp.zeros((1, 2, 128, 128), jnp.float32)
    assert not la._use_kernel(tiles)
    monkeypatch.setattr(la, "_on_one_tpu", lambda: True)
    assert la._use_kernel(tiles)
    assert not la._use_kernel(jnp.zeros((1, 2, 16, 16), jnp.float32))
    assert not la._use_kernel(tiles.astype(jnp.bfloat16))
    for H in (3, 8, 32, 40, 64, 96):
        plan = la.step_plan(H, 128, 128)
        assert plan.heads <= 32 and plan.heads % plan.unroll == 0
        assert plan.heads == H or plan.heads % 8 == 0
        assert plan.vmem_bytes(128, 128) <= 32 << 20
