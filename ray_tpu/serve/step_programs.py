"""The serving engine's device programs.

What ``serve/engine.py``'s host loop dispatches, and nothing of the
loop itself: the chunked-prefill, decode and spec-verify step programs
(``jit_prefill``, ``jit_decode``, ``jit_verify`` in a device trace) and
the three small ones that move a page or seed a slot.

The step programs close over nothing of one engine but its static
shape/sampling knobs, so they are built once per distinct knob set
and shared by every engine in the process: a pool's replicas (and a
restarted replica) trace each step once instead of once per engine,
and jit's own cache keys the executables by shape, dtype and device.
``mesh`` is the replica's EngineSharding mesh or None: it decides the
KV-pool sharding constraint and is ambient while the model is traced
(``ambient_mesh``), and a replica rebuilt over the same devices hashes
to the same entry.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models.kv_cache import (block_decode, kv_layer_store,
                                     kv_layer_view)
from ray_tpu.models.mixtral import stats_sections


def ambient_mesh(mesh):
    """The context a step program's model is traced in: a sharded
    replica's mesh made ambient, nothing for ``mesh`` None. Every rule
    that chooses between a Mosaic kernel and its XLA form asks for the
    ambient mesh (ops/grouped_matmul.py ``on_one_tpu``: GSPMD cannot
    partition a Mosaic kernel, so under a multi-device mesh the XLA
    form serves), and the engine asks the same rules under the same
    context for its counters."""
    return (contextlib.nullcontext() if mesh is None else
            jax.sharding.use_abstract_mesh(mesh.abstract_mesh))


def _moe_apply(model, mesh):
    """``model.apply`` for a step program, traced under the replica's
    mesh (``ambient_mesh``), a dense model's as a mixture's. For a
    model that counts on the device (models/mixtral.py
    ``stats_sections``: a mixture's routing, a selection's entries)
    the third result is (the int32 vector,) of its sections' counts
    over the program's live tokens, each reduced from the collection
    its layers sow into (``live()`` gives the [B, T] mask: a prefill
    row's real positions, a riding slot's one position of a decode
    step or, in the block program, the whole block of a slot that is
    still owed tokens) and
    concatenated in the sections' order; for any other model it is ().
    ``logits_at`` [B]: the one
    position of each row the program wants logits for, ``[B, V]``
    (models/llama.py transformer_forward, which then also runs the
    model's trailing layers that keep no entry at that position alone);
    None: every position's."""
    sections = stats_sections(model.config)
    sown_by = [s.collection for s in sections]

    def apply(params, ids, kv, start, live, logits_at=None):
        with ambient_mesh(mesh):
            (logits, new_kv), sown = model.apply(
                params, ids, kv_caches=kv, cache_len=start,
                logits_at=logits_at, mutable=sown_by)
        if not sections:
            return logits, new_kv, ()
        with jax.named_scope("moe_stats"):
            parts = [s.reduce(sown[s.collection], live()) for s in sections]
            vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return logits, new_kv, (vec,)
    return apply


def _views(pages, page_table, live, slots=None):
    """Every layer's entry of the pool as its layer consumes it
    (models/kv_cache.py kv_layer_view): a paged layer over the call's
    page table, a recurrent layer over the rows' ``slots`` (None: row
    i is slot i) and the [B, T] positions ``live()`` gives (nothing
    calls it for a model with pages only; the block program nulls the
    table's rows of slots that are owed nothing instead, so that they
    write no page). kv_layer_view/store
    keep the builders kind- and dtype-agnostic: fp layers are
    (pk, pv), int8 layers (pk, pv, sk, sv) — the scales ride the same
    donated tuple through the step."""
    return [kv_layer_view(layer, page_table, slots, live)
            for layer in pages]


def _constrain_for(mesh):
    """Pin a jitted step's output KV pool to the head-sharded layout
    (identity unsharded). Keeps GSPMD from ever resharding the pool
    mid-graph — resharding would break the donate-and-alias
    discipline AND introduce KV collectives."""
    if mesh is None:
        return lambda pages: pages
    from ray_tpu.serve.sharding import constrain_kv_pool
    return functools.partial(constrain_kv_pool, mesh)


@functools.lru_cache(maxsize=64)
def _jit_write_page(mesh):
    """Jitted whole-page landing write: scatter one pulled page's
    per-layer columns (k/v payload and, for int8 pools, their
    per-page scales — they travel together) into physical page
    ``dst`` across every layer. dst is a traced scalar: one
    executable for the whole pull. The donated pool update is the
    same in-place discipline every other jitted step uses."""
    constrain = _constrain_for(mesh)

    def write(pages, dst, cols):
        return constrain(
            [tuple(t.at[dst].set(c)
                   for t, c in zip(layer, layer_cols))
             for layer, layer_cols in zip(pages, cols)])
    return jax.jit(write, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _jit_prefill(model, temp, B, capture, mesh):
    """The chunked-prefill program: [B, T] token ids at per-row start
    offsets scatter into the rows' pages (append-at-offset) and
    attend causally over each row's own page window. The row's last
    real position samples a candidate first token — junk for rows
    mid-prompt, consumed only for rows that just finished their
    prompt. The model is asked for that one position's logits a row
    (``logits_at``): the head sees [B, dim], and the program holds no
    [B, T, V] value. What else the model leaves out for it is the
    model's to say: the layers after its last layer that keeps an entry
    (models/kv_cache.py ``sampled_only_from``: none for most models,
    Phi-4-mini-flash's cross-decoder) write nothing a later call could
    read, so they run at that one position a row, a decode step's
    shape, and every entry of the pool is what the every-position call
    would leave. The decode and verify programs ask for every position
    they hold and are not narrowed."""
    constrain = _constrain_for(mesh)
    apply = _moe_apply(model, mesh)
    from ray_tpu.models.llama import _pick_token

    def prefill(params, pages, ids, start, last_idx, page_table,
                rng, slots=None):
        rng, sub = jax.random.split(rng)
        # live tokens: a real row's positions up to its last real one
        # (dummy rows point at the null page; the rest is padding).
        # ``slots`` [B]: the decode slot each row belongs to, which
        # only a model with recurrent layers reads (a dummy row's is
        # out of range: it reads zeros and writes nothing)
        def live():
            return (page_table[:, :1] != 0) & (
                jnp.arange(ids.shape[1])[None] <= last_idx[:, None])
        last, new_kv, moe = apply(
            params, ids, _views(pages, page_table, live, slots), start,
            live, last_idx)                           # [B, V]
        new_pages = constrain([kv_layer_store(c) for c in new_kv])
        with jax.named_scope("sample"):
            firsts = _pick_token(last, sub, temp)
        if capture:
            # Score under the SAMPLING distribution (temperature-
            # scaled at temp > 0) — the behavior policy an RL
            # learner's importance ratio needs, not the raw model
            # distribution.
            slog = (last.astype(jnp.float32) / temp if temp > 0.0
                    else last.astype(jnp.float32))
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(slog),
                firsts[:, None], axis=-1)[:, 0]
            return ((firsts, lp), new_pages, rng) + moe
        return (firsts, new_pages, rng) + moe

    return jax.jit(prefill, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def _jit_verify(model, mesh):
    """The spec-verify program for rows of ``spec_len + 1``: [S, T]
    rows of [cur, drafts...] scatter into each slot's pages at its
    own offset and attend causally over the slot's page window — the
    exact chunked-prefill path, reused at decode offsets. Greedy by
    construction: position j's argmax is the token plain
    temperature-0 decode would have emitted after input j, so
    acceptance is a pure prefix compare on the host. No rng
    threading — speculation is disabled at temperature > 0."""
    constrain = _constrain_for(mesh)
    apply = _moe_apply(model, mesh)

    def verify(params, pages, ids, start, page_table):
        # every position of a verified slot's row is a forward pass,
        # unused draft places included; row i is slot i
        def live():
            return jnp.broadcast_to(page_table[:, :1] != 0, ids.shape)
        logits, new_kv, moe = apply(
            params, ids, _views(pages, page_table, live), start, live)
        new_pages = constrain([kv_layer_store(c) for c in new_kv])
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                new_pages) + moe

    return jax.jit(verify, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def _jit_decode(model, temp, KMAX, S, capture, mesh):
    constrain = _constrain_for(mesh)
    apply = _moe_apply(model, mesh)
    moe_len = sum(len(s) for s in stats_sections(model.config))
    from ray_tpu.models.llama import _pick_token

    def decode(params, pages, page_table, pos, cur, rng, steps):
        # fori_loop with a RUNTIME bound: one executable serves
        # every dispatch length (chunk-sized quick syncs and full
        # run-ahead alike); tokens land in a fixed [KMAX, S]
        # buffer, rows past `steps` stay zero and are never read.
        # pos/cur are the DEVICE-authoritative per-slot state:
        # they chain dispatch-to-dispatch (admission seeds rows
        # via _jit_seed's scatter), so no host readback ever
        # sits between two dispatches. With logprob capture a
        # float32 [KMAX, S] buffer of the chosen tokens' logprobs
        # rides the same carry and the same trailing readback.
        buf0 = jnp.zeros((KMAX, S), jnp.int32)
        lp0 = jnp.zeros((KMAX, S), jnp.float32)

        # a mixture-of-experts model's routing counters ride the
        # carry too, summed over the steps (riders only: the other
        # slots' page-table rows are null)
        moe0 = (jnp.zeros((moe_len,), jnp.int32),) if moe_len else ()
        # row i is slot i; a slot that rides without a request (its
        # page-table row is null) moves no recurrent state either
        def live():
            return page_table[:, :1] != 0

        def body(i, carry):
            pages, pos, cur, key, buf, lps, *moe = carry
            key, sub = jax.random.split(key)
            logits, new_kv, vec = apply(
                params, cur[:, None], _views(pages, page_table, live),
                pos, live)
            moe = tuple(m + v for m, v in zip(moe, vec))
            with jax.named_scope("sample"):
                nxt = _pick_token(logits[:, -1], sub, temp)
            if capture:
                # Behavior-policy logprob: temperature-scaled to
                # match what _pick_token actually sampled from.
                slog = (logits[:, -1].astype(jnp.float32) / temp
                        if temp > 0.0
                        else logits[:, -1].astype(jnp.float32))
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(slog),
                    nxt[:, None], axis=-1)[:, 0]
                lps = lps.at[i].set(lp)
            # pin the loop-carried pool to the head-sharded layout
            # so the carry's sharding is loop-invariant (GSPMD
            # would otherwise be free to reshard mid-carry)
            new_pages = constrain(
                [kv_layer_store(c) for c in new_kv])
            return (new_pages, pos + 1, nxt, key,
                    buf.at[i].set(nxt), lps) + moe
        pages, pos, cur, key, buf, lps, *moe = jax.lax.fori_loop(
            0, steps, body, (pages, pos, cur, rng, buf0, lp0) + moe0)
        # key/pos/cur return as device state: the host never syncs
        # on them between dispatches
        out = (buf, lps) if capture else buf
        return (out, pages, key, pos, cur) + tuple(moe)  # buf: [KMAX, S]

    return jax.jit(decode, donate_argnums=(1, 3, 4))


# The int32 counters a block program sums over a dispatch, in the
# order of its vector (serve/round_accounts.py reports each under
# ``denoise_`` in the ``round`` event): forwards ridden by a slot that
# was still owed tokens; those of them that were commits; positions
# revealed; tokens emitted (a committed block's, less its prompt
# remainder, cut at the request's budget and at an eos); forwards a
# slot rode AFTER its last commit (a dispatch planned on the bound
# outlasts a slot that finished early: it idles, writes nothing and is
# counted here).
BLOCK_COUNTERS = ("rider_forwards", "commits", "revealed", "emitted",
                  "idle_forwards")


def block_state(S: int, L: int):
    """The device-authoritative state a slot of a block program, zeros:
    (``pos`` [S] the block's start, ``left`` [S] tokens the request is
    still owed, ``blk`` [S, L] the block's tokens, ``masked`` [S, L] its
    flags, ``lead`` [S] the leading positions of the block that are the
    prompt's remainder and not generated, ``step`` [S] the denoising
    steps the block has had). Donated and chained dispatch to dispatch
    as ``pos``/``cur`` are."""
    z = functools.partial(jnp.zeros, (S,), jnp.int32)
    return (z(), z(), jnp.zeros((S, L), jnp.int32),
            jnp.zeros((S, L), jnp.bool_), z(), z())


@functools.lru_cache(maxsize=64)
def _jit_decode_blocks(model, temp, KMAX, S, eos_id, mesh):
    """The decode program of a model that DECODES BY BLOCKS
    (models/kv_cache.py ``BlockDecode``), built where ``_jit_decode``
    is and chosen by what the config says; an engine builds one of the
    two, and in a device trace this one is ``jit_decode`` too.

    A step of the runtime-bound ``fori_loop`` is a FORWARD of every
    slot's block ``[S, L]`` at the block's start ``pos`` over the
    slot's pages under the block mask (a masked position's input is
    the mask token's). Per slot, by its own flags: no flag set, and
    this forward WAS the commit (it wrote the finished block's K/V):
    the block's generated tokens go to the step's row of the output
    ``[KMAX, S, L]`` with their count (the block's leading prompt
    remainder is not counted, the count is cut at ``left`` and at an
    ``eos_id``, which ends the request), ``pos += L``, ``left -=
    count`` and the next block opens all masked. Else the forward's
    logits AT the masked positions choose tokens (argmax, or
    ``_pick_token`` at a temperature) with their confidence (the chosen
    token's probability, float32) and the config's ``remasking``
    reveals some: ``sequential`` the first n_s masked,
    ``low_confidence_static`` the n_s most confident,
    ``low_confidence_dynamic`` every one above the threshold if those
    are at least n_s, else the n_s most confident (n_s:
    ``BlockDecode.transfer_counts`` at the block's step; ties go to
    the lower position). Revealed tokens stay. A slot with ``left ==
    0`` or a null page-table row is not live: its row of the table is
    nulled for the forward, so it writes no page, moves nothing, is
    given no expert and its routing is not counted.

    Returns ((tokens [KMAX, S, L], counts [KMAX, S], the positions
    each forward revealed as a bit mask [KMAX, S], ``BLOCK_COUNTERS``
    summed over the dispatch), pages, key, state) and the model's own
    counter vector, as ``_jit_decode`` does."""
    constrain = _constrain_for(mesh)
    apply = _moe_apply(model, mesh)
    moe_len = sum(len(s) for s in stats_sections(model.config))
    bd = block_decode(model.config)
    L = bd.block_length
    if L > 30:
        raise ValueError(f"a block of {L} positions does not fit the "
                         f"reveal mask's 30 bits")
    from ray_tpu.models.llama import _pick_token

    def reveal_of(masked, conf, step):
        """[S, L] bool: the masked positions this forward reveals."""
        n_s = jnp.asarray(bd.transfer_counts(), jnp.int32)[
            jnp.minimum(step, bd.denoising_steps - 1)][:, None]
        at = jnp.arange(L)
        if bd.remasking == "sequential":
            rank = jnp.cumsum(masked, axis=1) - 1
            return masked & (rank < n_s)
        c = jnp.where(masked, conf, -jnp.inf)
        # a position's rank among the masked: those more confident, and
        # of the equally confident those before it
        ahead = (c[:, None, :] > c[:, :, None]) | (
            (c[:, None, :] == c[:, :, None])
            & (at[None, None, :] < at[None, :, None]))
        rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
        top = masked & (rank < n_s)
        if bd.remasking == "low_confidence_static":
            return top
        high = masked & (conf > bd.confidence_threshold)
        enough = jnp.sum(high, axis=1, keepdims=True) >= n_s
        return jnp.where(enough, high, top)

    def decode(params, pages, page_table, state, rng, steps):
        buf0 = jnp.zeros((KMAX, S, L), jnp.int32)
        cnt0 = jnp.zeros((KMAX, S), jnp.int32)
        tally0 = jnp.zeros((len(BLOCK_COUNTERS),), jnp.int32)
        moe0 = (jnp.zeros((moe_len,), jnp.int32),) if moe_len else ()
        riding = page_table[:, 0] != 0
        at = jnp.arange(L)[None]

        def body(i, carry):
            pages, (pos, left, blk, masked, lead, step), key, \
                buf, cnt, rev, tally, *moe = carry
            key, sub = jax.random.split(key)
            live_s = riding & (left > 0)
            # a slot that is owed nothing rides as a free one does
            table = jnp.where(live_s[:, None], page_table, 0)

            def live():
                return jnp.broadcast_to(live_s[:, None], (S, L))
            ids = jnp.where(masked, bd.mask_token_id, blk)
            logits, new_kv, vec = apply(
                params, ids, _views(pages, table, live), pos, live)
            moe = tuple(m + v for m, v in zip(moe, vec))
            with jax.named_scope("sample"):
                # (float32 from the head) what _pick_token samples from
                lg = logits / temp if temp > 0.0 else logits
                chosen = _pick_token(logits, sub, temp)         # [S, L]
                conf = jnp.exp(jnp.take_along_axis(
                    lg, chosen[..., None], axis=-1)[..., 0]
                    - jax.nn.logsumexp(lg, axis=-1))
                commit = live_s & ~jnp.any(masked, axis=1)
                reveal = reveal_of(masked, conf, step) & \
                    live_s[:, None]
                blk = jnp.where(reveal, chosen, blk)
                masked = masked & ~reveal
                # the committed block's generated tokens, from column 0
                row = jnp.take_along_axis(
                    blk, (at + lead[:, None]) % L, axis=1)
                count = jnp.minimum(L - lead, left)
                done = jnp.zeros((S,), jnp.bool_)
                if eos_id is not None:
                    hit = (row == eos_id) & (at < count[:, None])
                    done = jnp.any(hit, axis=1)
                    count = jnp.where(done, jnp.argmax(hit, axis=1) + 1,
                                      count)
                count = jnp.where(commit, count, 0)
                buf = buf.at[i].set(jnp.where(commit[:, None], row, 0))
                cnt = cnt.at[i].set(count)
                rev = rev.at[i].set(jnp.sum(
                    reveal.astype(jnp.int32) << at, axis=1))
                tally = tally + jnp.stack([
                    jnp.sum(live_s), jnp.sum(commit), jnp.sum(reveal),
                    jnp.sum(count), jnp.sum(riding & ~live_s)]
                ).astype(jnp.int32)
                pos = jnp.where(commit, pos + L, pos)
                left = jnp.where(commit & done, 0, left - count)
                masked = masked | commit[:, None]
                lead = jnp.where(commit, 0, lead)
                step = jnp.where(commit, 0, step + live_s)
            new_pages = constrain(
                [kv_layer_store(c) for c in new_kv])
            return (new_pages, (pos, left, blk, masked, lead, step), key,
                    buf, cnt, rev, tally) + moe
        pages, state, key, buf, cnt, rev, tally, *moe = \
            jax.lax.fori_loop(
                0, steps, body,
                (pages, state, rng, buf0, cnt0, cnt0, tally0) + moe0)
        return ((buf, cnt, rev, tally), pages, key, state) + tuple(moe)

    return jax.jit(decode, donate_argnums=(1, 3))


@functools.lru_cache(maxsize=64)
def _jit_seed_blocks():
    """``_jit_seed`` for a block program: scatter admitted rows' state
    into the device decode state (``block_state``): the block's start,
    the tokens owed, and the first block, which opens with the prompt's
    remainder (``lead`` tokens, flags clear) beside masks. Rows padded
    with ix == S drop. Nothing of the prefill call is read: a model that
    decodes by blocks has no first token from prefill."""
    def seed_blocks(state, ixs, pos, left, blk, lead):
        p, l, b, m, ld, st = state
        masked = jnp.arange(b.shape[1])[None] >= lead[:, None]

        def put(old, new):
            return old.at[ixs].set(new, mode="drop")
        return (put(p, pos), put(l, left), put(b, blk), put(m, masked),
                put(ld, lead), put(st, jnp.zeros_like(lead)))
    return jax.jit(seed_blocks, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _jit_copy_page(mesh):
    """Jitted whole-page copy across every layer's K and V pool:
    the prefix cache's one COW copy, used when an admission's
    prompt is FULLY cached — the final matched page is duplicated
    into a private page so the one-token re-prefill (the model
    needs the last position's logits) never scatters into a
    shared page. src/dst are traced scalars: one executable.
    Under tensor parallelism the copy stays device-local: the
    sharded kv-head axis is untouched, each device duplicates its
    own head shard of the page."""
    constrain = _constrain_for(mesh)

    def copy(pages, src, dst):
        # int8 layers are 4-tuples whose trailing scale tensors
        # copy their (rank-2) page row the same way — COW gets
        # the page's quantization scale for free, so a COW'd page
        # dequantizes identically to its source
        return constrain([tuple(t.at[dst].set(t[src])
                                for t in layer)
                          for layer in pages])
    return jax.jit(copy, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def _jit_seed():
    """Jitted admission seeding: scatter a prefill batch's first
    tokens and write positions into the device decode state.
    Rows padded with ix == S drop (mode='drop') — one executable
    regardless of how many slots the group filled."""
    def seed(dev_cur, dev_pos, firsts, ixs, rows, posv):
        return (dev_cur.at[ixs].set(firsts[rows], mode="drop"),
                dev_pos.at[ixs].set(posv, mode="drop"))
    return jax.jit(seed, donate_argnums=(0, 1))
