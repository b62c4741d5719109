"""The sliding layers' kernel (ops/ring_window_attention.py) in
interpret mode against the form it replaces (ops/paged_attention.py
``ring_append`` + ``ring_attention`` over the rows' rings taken by
slot), on the CPU.

The form runs on float32 copies of the operands (the same values: this
CPU's runtime has no bfloat16 contraction over a batch of rows).

Tolerances. The rings written are compared EXACTLY: a block merged
under a 0/1 placement matrix reproduces every value. ``y`` differs in
the order of its sums (the new keys first, then the ring a block at a
time under an online softmax, where the form has one softmax over the
ring) and, in bfloat16, in ``p``'s rounding before the read-out and the
result's own: bfloat16 results of the order of 1 agree to 2e-2, float32
ones to 2e-5.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ring_window_attention as rw
from ray_tpu.ops.paged_attention import ring_append

H, KH, D = 16, 2, 128
WINDOW, L, CHUNK = 40, 128, 32          # two write-back blocks of 64


def _rings(slots, seed, dtype, scale=1.0, length=L):
    rng = np.random.default_rng(seed)
    shape = (slots, KH, length, D)
    return tuple(jnp.asarray(scale * rng.standard_normal(shape), dtype)
                 for _ in range(2))


def _chunk(B, T, seed, dtype):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((B, T, KH, D)), dtype)
            for _ in range(2))
    return q, k, v


def _form(q, k, v, ring_k, ring_v, slots, pos, valid):
    return rw.ring_pair(*(a.astype(jnp.float32)
                          for a in (q, k, v, ring_k, ring_v)),
                        slots, pos, valid, WINDOW)


def _both(q, k, v, rings, slots, pos, n_real, **kw):
    T = q.shape[1]
    valid = jnp.arange(T)[None] < jnp.asarray(n_real)[:, None]
    pos = jnp.asarray(pos, jnp.int32)
    slots = None if slots is None else jnp.asarray(slots, jnp.int32)
    want = _form(q, k, v, *rings, slots, pos, valid)
    got = rw.ring_window_kernel(q, k, v, *rings, slots, pos, valid,
                                window=WINDOW, interpret=True, **kw)
    return want, got, np.asarray(n_real)


def _fill(rings, slot, upto, seed, dtype):
    """A request's first ``upto`` positions in ``slot``'s rings, through
    the form."""
    for start in range(0, upto, CHUNK):
        n = min(CHUNK, upto - start)
        _q, k, v = _chunk(1, CHUNK, seed + start, dtype)
        rings = ring_append(*rings, jnp.asarray([slot]),
                            jnp.asarray([start], jnp.int32), k, v,
                            jnp.arange(CHUNK)[None] < n)
    return rings


# name: (slots of the pool, the rows' slots (None: row i is slot i),
# the rows' positions, their real tokens of a chunk (a decode step: one
# where not 0), what the slots held before: (slot, positions written))
CASES = {
    "a context shorter than the window": (
        3, [1], [7], [CHUNK], [(1, 7)]),
    "an append that wraps the ring's end": (
        3, [2], [L + L - 9], [CHUNK], [(2, L + L - 9)]),
    "rows of different lengths, padding behind": (
        4, [3, 0, 1], [50, 200, 0], [CHUNK, 5, 17],
        [(3, 50), (0, 200)]),
    "a row without a request, a stale pos, no slot": (
        3, [1, 3, 0], [90, 977, 64], [CHUNK, 0, 9],
        [(1, 90), (0, 64)]),
    "a re-used slot": (
        2, [1], [6], [11], [(1, 300), (1, 6)]),
    "several laps of the ring": (
        3, [0, 2], [5 * L + 70, 3 * L - 1], [CHUNK, CHUNK],
        [(0, 5 * L + 70), (2, 3 * L - 1)]),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("T", [1, CHUNK], ids=["step", "chunk"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_against_the_form(name, T, dtype):
    n_slots, slots, pos, n_real, held = CASES[name]
    rings = _rings(n_slots, 1, dtype, scale=30.0)    # a previous owner's
    for slot, upto in held:
        rings = _fill(rings, slot, upto, 100 * slot, dtype)
    if T == 1:
        n_real = [min(n, 1) for n in n_real]
    q, k, v = _chunk(len(pos), T, 7, dtype)
    (y, rk, rv), (y2, rk2, rv2), n_real = _both(
        q, k, v, rings, slots, pos, n_real)
    np.testing.assert_array_equal(np.asarray(rk2, np.float32),
                                  np.asarray(rk, np.float32))
    np.testing.assert_array_equal(np.asarray(rv2, np.float32),
                                  np.asarray(rv, np.float32))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for b, n in enumerate(n_real):
        np.testing.assert_allclose(
            np.asarray(y2[b, :n], np.float32),
            np.asarray(y[b, :n], np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("T", [1, CHUNK], ids=["step", "chunk"])
def test_slots_that_do_not_ride_keep_their_rings(T):
    """A decode call of 6 rows of which rows 1 and 4 ride, a prefill
    call whose rows name slots 4 and 1: every other slot's rings are bit
    for bit what they were, and so is every index of a riding slot's
    ring that its tokens do not land on."""
    rings = _rings(6, 3, jnp.bfloat16)
    if T == 1:
        slots, pos, n_real = None, [3, 70, 9, 0, 2 * L + 5, 1], \
            [0, 1, 0, 0, 1, 0]
        B, riding = 6, {1: (70, 1), 4: (2 * L + 5, 1)}
    else:
        slots, pos, n_real = [4, 9, 1], [L - 3, 0, 10], [CHUNK, 0, 12]
        B, riding = 3, {4: (L - 3, CHUNK), 1: (10, 12)}
    q, k, v = _chunk(B, T, 11, jnp.bfloat16)
    _want, (_y, rk, rv), _n = _both(q, k, v, rings, slots, pos, n_real)
    for before, after in zip(rings, (rk, rv)):
        before, after = (np.asarray(a, np.float32) for a in
                         (before, after))
        for s in range(6):
            kept = np.ones(L, bool)
            if s in riding:
                start, n = riding[s]
                kept[(start + np.arange(n)) % L] = False
                assert (after[s][:, ~kept] != before[s][:, ~kept]).any()
            np.testing.assert_array_equal(after[s][:, kept],
                                          before[s][:, kept])


@pytest.mark.parametrize("block", [128, 256])
def test_a_ring_in_several_folds(block):
    """A ring of 320 positions folded 128 or 256 keys at a time, the
    last fold what is left (64): the online softmax over the blocks
    against the one-piece form."""
    rings = _rings(2, 5, jnp.bfloat16, length=320)
    rings = _fill(rings, 1, 700, 9, jnp.bfloat16)
    for T in (1, CHUNK):
        q, k, v = _chunk(1, T, 13, jnp.bfloat16)
        (y, rk, _), (y2, rk2, _), _n = _both(
            q, k, v, rings, [1], [700], [T], key_block=block)
        np.testing.assert_array_equal(np.asarray(rk2, np.float32),
                                      np.asarray(rk, np.float32))
        np.testing.assert_allclose(np.asarray(y2, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_the_rule_reads_shapes_types_and_the_backend(monkeypatch):
    def shapes(T=1, dtype=jnp.bfloat16, heads=32, D=128, L=1344,
               window=1024):
        s = jax.ShapeDtypeStruct
        return (s((4, T, heads, D), dtype), s((4, T, 4, D), dtype),
                s((4, T, 4, D), dtype), s((32, 4, L, D), dtype),
                s((32, 4, L, D), dtype), window)
    assert not rw.applies(*shapes())                 # the CPU
    monkeypatch.setattr(rw, "_on_one_tpu", lambda: True)
    assert rw.applies(*shapes()) and rw.applies(*shapes(T=256))
    assert not rw.applies(*shapes(dtype=jnp.float32))
    assert not rw.applies(*shapes(D=64))
    assert not rw.applies(*shapes(T=8))              # half a sublane tile
    assert not rw.applies(*shapes(heads=8))          # of one token, too
    assert not rw.applies(*shapes(T=336))            # laps the window
    assert not rw.applies(*shapes(L=1336))           # no whole block
    assert rw.key_spans(32, 1344) == [(0, 1344)]
    assert rw.key_spans(2048, 1344)[-2:] == [(1024, 1280), (1280, 1344)]
    assert rw.kernel_keys(24, 1344) == 24 * 1344


def test_off_the_chip_the_entry_is_the_form():
    """On the CPU ``ring_window_attention`` is ``ring_append`` +
    ``ring_attention`` and holds no kernel: the same rings, the same
    ``y``."""
    rings = _rings(3, 2, jnp.float32)
    q, k, v = _chunk(2, CHUNK, 4, jnp.float32)
    slots, pos = jnp.asarray([2, 0]), jnp.asarray([33, 64], jnp.int32)
    valid = jnp.arange(CHUNK)[None] < jnp.asarray([CHUNK, 20])[:, None]
    entry = jax.jit(functools.partial(rw.ring_window_attention,
                                      window=WINDOW))
    text = entry.lower(q, k, v, *rings, slots, pos, valid).as_text()
    assert "ring_window" not in text and "scatter" in text
    y, rk, rv = entry(q, k, v, *rings, slots, pos, valid)
    want = _form(q, k, v, *rings, slots, pos, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for got, ref in zip((rk, rv), want[1:]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
