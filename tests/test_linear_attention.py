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
once through a unit lower-triangular system, inverted by forward
substitution: ``unit_lower_inverse``, held here to XLA's own
``triangular_solve`` and to closed forms on the inputs a series would
fail); with l2-normed keys the state and the
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
# The two gates, each at its model's kind of head: a decay a CHANNEL
# over square heads (Kimi Delta Attention: 128 x 128 served), ONE decay
# a head over heads whose values are twice as wide as their keys and
# neither a multiple of the other's tile (Gated DeltaNet: 96 x 192
# served).
GATES = {"channel": (D, D), "head": (12, 24)}
BOTH = pytest.mark.parametrize("gate", sorted(GATES))


def _inputs(T, seed=0, hard=True, gate="channel", equal_keys=False):
    """Unit keys (a head's ALL EQUAL where ``equal_keys``: a prompt that
    repeats one token), values of order 1, beta up to 2 and log-decays
    (a channel, or a head) from -0.001 down to -20 a step where
    ``hard``."""
    dk, dv = GATES[gate]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = l2norm(jax.random.normal(ks[1], (B, 1 if equal_keys else T, H, dk)))
    k = jnp.broadcast_to(k, (B, T, H, dk))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    shape = (B, T, H, dk) if gate == "channel" else (B, T, H)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0,
                                    maxval=3.0 if hard else -3.0))
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, state


def _a_channel(g, k):
    """A gate a head as the per-channel form and the scan take it:
    broadcast over the key's channels."""
    return g if g.ndim == k.ndim else jnp.broadcast_to(g[..., None],
                                                       k.shape)


def _scan(q, k, v, g, beta, state):
    with jax.default_matmul_precision("highest"):
        return delta_rule_scan(q, k, v, _a_channel(g, k), beta, state)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


@BOTH
@pytest.mark.parametrize("T,chunk,equal_keys", [
    (150, 64, False), (64, 64, False), (40, 16, False), (7, 64, False),
    (129, 32, False), (150, None, False), (150, None, True),
    (128, 128, True)])
def test_chunked_matches_the_scan(T, chunk, equal_keys, gate):
    """T not a multiple of the chunk, hard decays (g down to -20 a
    step: exp(+20 x 64) would overflow a factored form), beta to 2, a
    state that is not zero; the gate's own chunk where none is given. A
    row of EQUAL keys under decays of a thousandth to a twentieth a
    step: the chunk's system is then nearly 2 x (the strictly lower
    ones), whose powers reach 1e18 while its inverse stays at +-2."""
    q, k, v, g, beta, state = _inputs(T, gate=gate, equal_keys=equal_keys,
                                      hard=not equal_keys)
    if T >= 40 and not equal_keys:
        assert float(g.min()) < (-19.0 if gate == "channel" else -15.0)
        assert float(beta.max()) > 1.99
    o, s = jax.jit(kda_chunked, static_argnames="chunk")(
        q, k, v, g, beta, state, chunk=chunk)
    want_o, want_s = _scan(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(o)).all()
    _close(o, want_o)
    _close(s, want_s)


@BOTH
@pytest.mark.parametrize("equal_keys", [False, True])
def test_chunked_matches_a_float64_scan(equal_keys, gate):
    """The chunk form against the recurrence in FLOAT64, at a quarter of
    the file's tolerance: what float32 costs the chunk form itself (the
    masks' exponentials, the system's inverse, the sums' order), with no
    second float32 program's rounding in the way. Over six seeds it
    reads 0.02-0.14 of the file's tolerance, equal keys or not (the
    float32 scan 0.003-0.04)."""
    a = _inputs(150, gate=gate, equal_keys=equal_keys, hard=not equal_keys)
    got = jax.jit(kda_chunked)(*a)
    with jax.enable_x64(True):
        want = _scan(*(jnp.asarray(np.asarray(x), jnp.float64) for x in a))
        assert want[0].dtype == jnp.float64
        want = [np.asarray(w) for w in want]
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=RTOL / 4,
                                   atol=ATOL / 4)


def _systems(case, C, gate, seed=11):
    """A chunk's unit lower-triangular system as ``kda_chunked`` builds
    it, ``I + Diag(beta) strict(kk)`` with ``kk[t, i] = sum_c k_t[c]
    k_i[c] exp(G_t[c] - G_i[c])``, [B, H, C, C], and a right-hand side
    of ``dv`` columns. ``random``: unit keys, beta to 2, the gate's
    decays. ``equal-2`` / ``equal-1``: a head's keys ALL EQUAL, beta 2 /
    1, no decay. ``four-1.9``: four keys a head, each on a quarter of
    the chunk, beta 1.9, no decay."""
    dk, dv = GATES[gate]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    distinct = {"random": C, "equal-2": 1, "equal-1": 1, "four-1.9": 4}[case]
    k = l2norm(jax.random.normal(ks[0], (B, H, distinct, dk)))
    k = k[:, :, jnp.arange(C) * distinct // C]
    rhs = jax.random.normal(ks[1], (B, H, C, dv))
    if case == "random":
        beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[2],
                                                             (B, H, C)))
        shape = (B, H, C, dk) if gate == "channel" else (B, H, C, 1)
        G = jnp.cumsum(-jnp.exp(jax.random.uniform(
            ks[3], shape, minval=-7.0, maxval=3.0)), axis=2)
    else:
        beta = jnp.full((B, H, C), float(case.split("-")[1]))
        G = jnp.zeros((B, H, C, 1))
    diff = jnp.minimum(G[:, :, :, None] - G[:, :, None, :], 0.0)
    kk = jnp.sum(k[:, :, :, None] * k[:, :, None, :] * jnp.exp(diff), -1)
    return jnp.tril(beta[..., None] * kk, -1), rhs


@BOTH
@pytest.mark.parametrize("C", [7, 16, 32, 64, 128])
@pytest.mark.parametrize("case", ["random", "equal-2", "equal-1",
                                  "four-1.9"])
def test_the_inverse_is_the_triangular_solve(case, C, gate):
    """``unit_lower_inverse`` against XLA's ``triangular_solve`` of the
    same system: a short last chunk's rows (7), the per-channel chunk's
    (16), the per-head chunk's (64) and those beside it (32, 128),
    under both gates, on random keys and on the keys a prompt that repeats a token gives,
    where a series in the powers of the system cancels to garbage in
    float32 (at C = 64: 2e18 of the largest true entry at beta 2) and
    substitution does not. Equal keys have closed forms: at beta 1 the
    inverse is the difference operator (1 on the diagonal, -1 under
    it); at beta 2 x_t = b_t - 2 s_(t-1) with s_t = b_t - s_(t-1)."""
    below, rhs = _systems(case, C, gate)
    inverse = jax.jit(la.unit_lower_inverse)(below)
    assert not np.asarray(jnp.triu(inverse, 1)).any()
    assert (np.asarray(jnp.diagonal(inverse, axis1=-2, axis2=-1))
            == 1.0).all()
    b = np.asarray(rhs, np.float64)
    got = np.einsum("bhij,bhjv->bhiv", np.asarray(inverse, np.float64), b)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.lax.linalg.triangular_solve(
            jnp.eye(C) + below, rhs, left_side=True, lower=True,
            unit_diagonal=True))

    def close(got, want):
        """The file's tolerance on a system's solution as a whole: by
        its largest entry (equal keys at beta 2 are conditioned 7e3 at
        C = 64 and 3e4 at 128, their solutions reach 70, and XLA's own
        solve is 3e-5 to 5e-5 off a float64 one there)."""
        scale = np.maximum(1.0, np.abs(want).max(axis=(2, 3), keepdims=True))
        _close(got / scale, want / scale)
    close(got, want)
    if case == "equal-1":
        _close(inverse, jnp.broadcast_to(
            jnp.eye(C) - jnp.eye(C, k=-1), inverse.shape))
    if case == "equal-2":
        x, s = [], np.zeros_like(b[:, :, 0])
        for t in range(C):
            x.append(b[:, :, t] - 2.0 * s)
            s = b[:, :, t] - s
        close(got, np.stack(x, axis=2))


def _loops(jaxpr):
    """Every loop of a program, the nested ones too: (primitive, trips
    or None, its body)."""
    found = []
    for eq in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eq.params):
            if eq.primitive.name in ("scan", "while"):
                found.append((eq.primitive.name, eq.params.get("length"),
                              sub))
            found += _loops(sub)
    return found


def _primitives(jaxpr):
    names = {eq.primitive.name for eq in jaxpr.eqns}
    for eq in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eq.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("model,H_,dk,dv,a_channel", [
    ("olmo-hybrid", 30, 96, 192, False),    # 4 chunks of 64
    ("solar-open2", 64, 128, 128, True),    # 16 chunks of 16
    ("kimi-linear", 32, 128, 128, True)])
def test_a_chunk_is_solved_by_the_inverse(model, H_, dk, dv, a_channel):
    """The engagement check, on the program of a [4, 256] prefill call
    at the served shapes (traced, never run): no ``triangular_solve``
    anywhere, and the only row-by-row work a chunk does is ONE loop of
    the chunk's rows inside the scan over the chunks, all of the chunk's
    B x H systems a step, the batch on the last axis."""
    f32 = jnp.float32
    lead = (4, 256, H_)
    args = [jax.ShapeDtypeStruct(lead + (d,), f32) for d in (dk, dk, dv)]
    args += [jax.ShapeDtypeStruct(lead + ((dk,) if a_channel else ()), f32),
             jax.ShapeDtypeStruct(lead, f32),
             jax.ShapeDtypeStruct((4, H_, dk, dv), f32)]
    jaxpr = jax.make_jaxpr(kda_chunked)(*args).jaxpr
    assert "triangular_solve" not in _primitives(jaxpr)
    C = la._CHUNK_PER_CHANNEL if a_channel else la._CHUNK_PER_HEAD
    (name, trips, chunk), = _loops(jaxpr)[:1]
    assert (name, trips) == ("scan", 256 // C)
    (name, trips, step), = _loops(chunk)
    assert (name, trips) == ("scan", C) and not _loops(step)
    carried, = (v.aval.shape for v in step.outvars if v.aval.ndim == 3)
    assert carried == (C, C, 4 * H_)


@BOTH
def test_step_looped_matches_the_scan(gate):
    T = 70
    q, k, v, g, beta, state = _inputs(T, seed=1, gate=gate)
    step = jax.jit(kda_step)
    outs, s = [], state
    for t in range(T):
        o, s = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o)
    want_o, want_s = _scan(q, k, v, g, beta, state)
    _close(jnp.stack(outs, axis=1), want_o)
    _close(s, want_s)


@BOTH
def test_padding_inside_a_row_moves_nothing(gate):
    """Rows of 100 and 37 real positions in a call of 150: the outputs
    at the real positions and the state left behind are those of the
    rows alone; a step that is not valid leaves the state as it was,
    and one that is ``fresh`` starts from zeros whatever it held."""
    T, real = 150, (100, 37)
    q, k, v, g, beta, state = _inputs(T, seed=2, gate=gate)
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
    one = tuple(a[:, 0] for a in (q, k, v, g, beta))
    held = state.at[0].set(jnp.inf)         # what a slot held is unread
    o2, s2 = kda_step(*one, held, None, jnp.asarray([True, False]))
    o0, s0 = kda_step(*one, state.at[0].set(0.0))
    assert (np.asarray(s2) == np.asarray(s0)).all()
    assert (np.asarray(o2) == np.asarray(o0)).all()


@BOTH
def test_two_calls_with_the_state_handed_over(gate):
    """A row in two calls (two engine rounds), then decode steps: the
    state carries everything."""
    T, cut = 120, 72
    q, k, v, g, beta, state = _inputs(T + 3, seed=3, gate=gate)
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


@BOTH
def test_a_bfloat16_state_would_fail(gate):
    """The same two calls with the state rounded to bfloat16 between
    them: outside the tolerance by far (mild decays, so the first
    call's state still matters in the second)."""
    T, cut = 120, 72
    q, k, v, g, beta, state = _inputs(T, seed=3, hard=False, gate=gate)
    first = tuple(a[:, :cut] for a in (q, k, v, g, beta))
    second = tuple(a[:, cut:] for a in (q, k, v, g, beta))
    _, s1 = kda_chunked(*first, state, chunk=64)
    rounded = s1.astype(jnp.bfloat16).astype(jnp.float32)
    o2, _ = kda_chunked(*second, rounded, chunk=64)
    want_o, _ = _scan(q, k, v, g, beta, state)
    gap = np.abs(np.asarray(o2) - np.asarray(want_o[:, cut:])).max()
    assert gap > 50 * ATOL, gap


@pytest.mark.parametrize("T,chunk", [(150, 64), (40, 16), (1, None)])
def test_a_gate_a_head_is_the_per_channel_form_fed_it_broadcast(T, chunk):
    """ONE decay a head through its own form (matmuls under a [C, C]
    mask; a step) against the per-channel form handed the same gate
    repeated over the 12 channels: one recurrence, two programs."""
    q, k, v, g, beta, state = _inputs(T, seed=7, gate="head")
    wide = _a_channel(g, k)
    if T == 1:
        one = tuple(a[:, 0] for a in (q, k, v, g, beta))
        got = kda_step(*one, state)
        want = kda_step(*one[:3], wide[:, 0], one[4], state)
    else:
        got = kda_chunked(q, k, v, g, beta, state, chunk=chunk)
        want = kda_chunked(q, k, v, wide, beta, state, chunk=chunk)
    _close(got[0], want[0])
    _close(got[1], want[1])


@BOTH
@pytest.mark.parametrize("p", [1, 3])
def test_a_packed_state_steps_as_the_state_a_head_at_a_time(gate, p):
    """``p`` heads side by side on the lanes ([B, H / p, dk, p x dv], as
    a state whose values are no whole lane tile is stored): packing is
    undone by unpacking, and a step over the packed state, with a row
    that rides nothing and a row that starts from zeros whatever it
    held, is the step over the heads one at a time, packed."""
    q, k, v, g, beta, state = _inputs(1, seed=8, gate=gate)
    one = tuple(a[:, 0] for a in (q, k, v, g, beta))
    packed = la.pack_heads(state, p)
    dk, dv = GATES[gate]
    assert packed.shape == (B, H // p, dk, p * dv)
    assert (np.asarray(la.unpack_heads(packed, p))
            == np.asarray(state)).all()
    for valid, fresh in [(None, None),
                         (jnp.asarray([True, False]),
                          jnp.asarray([True, False]))]:
        held = state if fresh is None else state.at[0].set(jnp.inf)
        want_o, want_s = kda_step(*one, held, valid, fresh)
        o, s = jax.jit(kda_step)(*one, la.pack_heads(held, p), valid, fresh)
        rows = slice(None) if valid is None else np.asarray(valid)
        _close(o[rows], want_o[rows])
        _close(la.unpack_heads(s, p), want_s)
    assert (np.asarray(s[1]) == np.asarray(packed[1])).all()


def test_the_per_head_chunk_holds_no_product_over_channels():
    """What the gate a head buys: the chunked program makes no array of
    [C, C, dk] (the per-channel form's decay products on the vector
    unit), and its own chunk is 64 where that form's is 16."""
    def largest(gate):
        args = _inputs(256, gate=gate)
        jaxpr = jax.make_jaxpr(kda_chunked)(*args)
        scan, = (eq for eq in jaxpr.jaxpr.eqns
                 if eq.primitive.name == "scan")
        return max(int(np.prod(v.aval.shape))
                   for e in scan.params["jaxpr"].jaxpr.eqns
                   for v in e.outvars)
    dk, dv = GATES["head"]
    assert la._CHUNK_PER_HEAD == 64 and la._CHUNK_PER_CHANNEL == 16
    # the scan's body: nothing larger than a chunk's [C, C] or [C, dv]
    assert largest("head") <= B * H * 64 * max(64, dv)
    assert largest("channel") == B * H * 16 * 16 * D


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


def _packed_inputs(n, H, dk, dv, seed=9):
    """One token for n slots of H heads of dk x dv under ONE decay a
    head: unit keys, beta up to 2, log-decays from -0.001 down to -20,
    a state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (n, H, dk))) * dk ** -0.5
    k = l2norm(jax.random.normal(ks[1], (n, H, dk)))
    v = jax.random.normal(ks[2], (n, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, H), minval=-7.0, maxval=3.0))
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (n, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (n, H, dk, dv))


# (heads, dk, dv, heads side by side, groups a loop step): the served
# 96 x 192 two by two (a lane tile shared by both heads of a group) at
# the rule's own unroll and at one; four heads of 32 to a single tile;
# two heads of whole tiles each; keys of a whole lane tile (the head's
# three numbers then lie in a second tile of the key's row)
@pytest.mark.parametrize("H,dk,dv,p,unroll", [
    (6, 96, 192, 2, None), (6, 96, 192, 2, 1), (8, 16, 32, 4, 2),
    (4, 8, 128, 2, None), (2, 128, 64, 2, None)])
def test_packed_kernel_matches_the_step(H, dk, dv, p, unroll):
    """Five slots: two plain, one that STARTS a request (from zeros,
    whatever the slot held), one that rides nothing (its state comes
    back bit for bit and its read-out is zeros), one plain after it."""
    one, state = _packed_inputs(5, H, dk, dv)
    assert float(one[4].max()) > 1.9 and float(one[3].min()) < -4.0
    state = state.at[2, 0].set(jnp.inf)     # what a slot held is unread
    valid = jnp.asarray([True, True, True, False, True])
    fresh = jnp.asarray([False, False, True, False, False])
    packed = la.pack_heads(state, p)
    o, s = la.kda_step_packed_kernel(*one, packed, valid, fresh,
                                     unroll=unroll, interpret=True)
    want_o, want_s = kda_step(*one, state, valid, fresh)
    rides = np.asarray(valid)
    _close(o[rides], want_o[rides])
    _close(la.unpack_heads(s, p), want_s)
    assert (np.asarray(s[3]) == np.asarray(packed[3])).all()
    assert not np.asarray(o[3]).any()
    # the jax.numpy form over the same packed state: one recurrence
    xla_o, xla_s = la._kda_step_packed(*one[:3], one[3][..., None], one[4],
                                       packed, valid, fresh)
    _close(o[rides], xla_o[rides])
    _close(s, xla_s)


@pytest.mark.parametrize("riding", [(), (1,), (0, 2)])
def test_packed_kernel_visits_the_riding_rows_whichever_they_are(riding):
    one, state = _packed_inputs(3, 4, 16, 64, seed=10)
    valid = jnp.zeros((3,), bool).at[jnp.asarray(riding, int)].set(True)
    packed = la.pack_heads(state, 2)
    o, s = la.kda_step_packed_kernel(*one, packed, valid, None,
                                     interpret=True)
    want_o, want_s = kda_step(*one, state, valid, None)
    for row in range(3):
        if row in riding:
            _close(o[row], want_o[row])
            _close(la.unpack_heads(s, 2)[row], want_s[row])
        else:
            assert (np.asarray(s[row]) == np.asarray(packed[row])).all()


def test_the_kernel_is_chosen_by_what_the_code_can_observe(monkeypatch):
    """On the CPU ``kda_step`` is the ``jax.numpy`` form; on one TPU it
    is the kernel where the heads tile, and only there."""
    tiles = jnp.zeros((1, 2, 128, 128), jnp.float32)
    assert not la._use_kernel(tiles)
    monkeypatch.setattr(la, "_on_one_tpu", lambda: True)
    assert la._use_kernel(tiles)
    assert not la._use_kernel(jnp.zeros((1, 2, 16, 16), jnp.float32))
    # one and a half lane tiles: 96 x 192 a head at a time keeps the
    # XLA form; two heads side by side under ONE decay a head get the
    # packed kernel, under a decay a channel, in another type, at keys
    # of no whole sublane tile or past a block they do not
    assert not la._use_kernel(jnp.zeros((1, 30, 96, 192), jnp.float32))
    served = jax.ShapeDtypeStruct((1, 15, 96, 384), jnp.float32)
    a_head = jax.ShapeDtypeStruct((1, 30, 1), jnp.float32)
    assert la._use_packed_kernel(served, a_head)
    assert not la._use_packed_kernel(
        served, jax.ShapeDtypeStruct((1, 30, 96), jnp.float32))
    for shape, dtype in (((1, 15, 96, 384), jnp.bfloat16),
                         ((1, 15, 96, 320), jnp.float32),
                         ((1, 15, 12, 384), jnp.float32),
                         ((1, 30, 96, 384), jnp.float32)):
        assert not la._use_packed_kernel(
            jax.ShapeDtypeStruct(shape, dtype), a_head), (shape, dtype)
    assert not la._use_kernel(tiles.astype(jnp.bfloat16))
    for H in (3, 8, 32, 40, 64, 96):
        plan = la.step_plan(H, 128, 128)
        assert plan.heads <= 32 and plan.heads % plan.unroll == 0
        assert plan.heads == H or plan.heads % 8 == 0
        assert plan.vmem_bytes(128, 128) <= 32 << 20
