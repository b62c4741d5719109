"""Paged KV cache: block-pool storage for continuous-batching decode.

vLLM-style paged attention re-thought for TPU/XLA (ref capability:
serve request batching, python/ray/serve/batching.py:46,215 — which
coalesces calls but decodes each batch to completion; this pool is the
structure that lets requests join/leave the decode batch per token):

- The KV pool is ONE static-shape array per layer,
  ``[n_pages, page_size, n_kv_heads, head_dim]`` — XLA never sees a
  dynamic allocation; the host-side ``BlockAllocator`` hands page ids
  to sequences as they grow and reclaims them on completion or
  preemption. The layout is PAGE-MAJOR: one physical page is one
  contiguous ``[page_size, n_kv_heads, head_dim]`` slab, which is
  what every step program moves — ``paged_append`` scatters whole
  ``[n_kv_heads, head_dim]`` rows at ``(page, offset)`` and the
  window loop gathers whole pages by id. Up to PR 28 the pool was
  declared head-major ``[KH, n_pages, Pg, D]`` for the Pallas decode
  kernel's sake, and the chip showed what that cost: the TPU
  compiler kept the pool page-major inside every step program
  anyway, so each program copied every layer's K and V pool into
  that layout on entry and back on exit — 64 whole-pool copies a
  dispatch at 16 layers, 14-15 % of three serving cells' chip time
  and a 2 GiB temporary (PERF.md section 6, PR 29). Declared as it
  is kept, no program copies it. (That kernel lost to the gather on
  the chip and went in PR 30.)
- Page 0 is the NULL page: inactive decode slots point their page
  table at it and harmlessly scatter their dead writes there, so the
  jitted decode step needs no ``lax.cond`` masking — every slot does
  identical work every step (SPMD-friendly, no divergence).
- Gather/scatter use plain advanced indexing: XLA lowers them to
  dynamic-gather/scatter HLO that tiles fine on TPU.
- ``kv_dtype="int8"`` halves page bytes: pages store int8 with one
  fp32 absmax scale per (physical page, kv_head) — shape
  ``[n_pages, n_kv_heads]``, page-major like the pages, so one page
  id indexes a page and its scales alike and the head axis shards
  with its head-sharded pages under tensor parallelism. Scales travel
  with page ids: the allocator, prefix cache, and COW path all deal
  in page ids only, and every consumer that moves a page
  (copy-on-write, placement, donation) moves the matching scale
  row in the same jitted op. Quantize/dequantize live in
  ops/paged_attention.py; nothing outside it interprets the int8
  payload.

SEVEN KINDS OF LAYER. The pool is a list of per-layer
entries BY KIND (``layer_kinds``): a layer of softmax attention has
K and V pages, as above; a layer of LATENT attention (models/axk1.py's
MLA) has pages too, handed out by the same allocator through the same
page table, but ONE pool of them, ``[n_pages, page_size,
latent_page_width(cfg)]``: a token's compressed key-value vector and
its shared rope key side by side, which every head reads (no V pool:
the values are a column prefix of the same entry; no head axis; the
entry stored in a whole number of the chip's 128-lane tiles, or the
chip's compiler lays the pool out with ``n_pages`` minor-most and
every step program copies it: PERF.md section 6, PR 34); a latent layer
that CHOOSES the entries a query attends (models/deepseek_v32.py) keeps,
beside that pool, a second one of the same pages for its indexer's keys,
``[n_pages, page_size, index_head_dim]``: one page id names a page of
both, so whatever deals in page ids (the allocator, the prefix cache, a
speculative rewind) carries the index keys with their latent entries
and knows nothing of them; a layer of linear
attention (models/solar_open2.py's KDA) has none, but a fixed-size
``RecurrentState`` a decode SLOT: the delta rule's matrix a head in
float32 and the last inputs of its short convolution, whatever the
context's length. ``page_layout`` is the one place that says what a
page of a kind is stored as. Pages are handed out by the allocator as
a sequence grows; a slot's state simply belongs to the slot. Nothing on the host ever clears it: the layer
itself starts a row whose write offset is 0 from zeros (as
``paged_append`` resets an int8 page's scale at offset 0), and rows
or positions that carry no request leave it as it was. A layer of
SLIDING-WINDOW attention (models/mellum.py) has none either, but a
``SlidingRing`` a decode slot: the keys and values of its last
``sliding_ring_len`` positions, position p at ring index p mod that
length, so its bytes a slot are a constant of the configuration and a
context eight times the window costs it what the window does. Nothing
clears a ring either: what a ring index holds is known from the row's
last written position alone (ops/paged_attention.py
``ring_attention``), and an index this request has not written is
never visible. Two kinds KEEP NOTHING (models/
phi4flash.py): a BORROWED layer attends the pages of the nearest K/V
layer before it (its OWNER: the same page table, the same page ids, the
owner's pages as they stand after the owner's append in the same call)
and a STATELESS layer reads no request state at all. Their entry of the
pool is the empty tuple: ``page_layout`` is ``()``, ``init_kv_pool``
makes nothing, a page costs and ships nothing for them, and since the
allocator, the page table, the prefix cache and a speculative rewind
deal in page ids they never learn of either: a page freed, handed out
again or refused is the owner's, and its readers follow. THE CONTRACT
of both kinds: given what it reads (its input at a position, the pages
it borrows, what an earlier layer of the same call published) a layer
that keeps no entry is POSITION-WISE, and a call that asks the model
for ``logits_at`` runs the model's trailing layers of these kinds at
the sampled position of each row alone (``sampled_only_from``, at the
file's end, says why that is sound). A model
whose layers are all of one kind declares nothing and gets the pool
it always had.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KV_SCALE_DTYPE = jnp.float32
KV_DTYPES = ("fp", "int8")


def check_kv_dtype(kv_dtype: Optional[str]) -> str:
    """The pool's storage dtype as a constructor argument names it:
    ``"fp"`` (or None) stores cfg.dtype pages, ``"int8"`` quantized
    pages with per-page scales. Anything else would silently serve
    from a pool the caller did not ask for, so it is an error."""
    if kv_dtype is None:
        return "fp"
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            "kv_dtype=%r is not supported (choose one of %s)" %
            (kv_dtype, ", ".join(repr(d) for d in KV_DTYPES)))
    return kv_dtype


KIND_KV = "kv"                  # a layer with K/V pages
KIND_RECURRENT = "recurrent"    # a layer with a fixed-size state a slot
KIND_LATENT = "latent"          # a layer with one pool of latent pages
KIND_SLIDING = "sliding"        # a layer with a ring of its window a slot
KIND_INDEXED = "indexed"        # latent pages AND pages of index keys
KIND_BORROWED = "borrowed"      # no entry: reads a K/V layer's pages
KIND_STATELESS = "stateless"    # no entry, and reads none
# not a kind of layer, but a row of KIND_REFUSALS beside them: a model
# whose decode step is a forward of a whole block (``block_decode``)
DECODES_BY_BLOCKS = "decodes_by_blocks"


def layer_kinds(cfg) -> Tuple[str, ...]:
    """Each layer's kind of per-request state: the config's own
    ``layer_kinds`` where it declares them, K/V pages everywhere
    otherwise."""
    return tuple(getattr(cfg, "layer_kinds", None)
                 or (KIND_KV,) * cfg.n_layers)


class BlockDecode(NamedTuple):
    """What a model that DECODES BY BLOCKS tells serving (a config's
    ``block_decode`` property; None, or absent, for every model that
    yields one token a sequence a step). Such a model was trained to
    fill in masked blocks: a decode step is a forward of a whole block
    of ``block_length`` positions under a BLOCK-CAUSAL mask (query i
    sees key j iff j // L <= i // L: causal across blocks,
    bidirectional inside one; L = 1 is the causal mask), the logits AT
    a masked position are for that position's own token, and
    ``denoising_steps`` forwards reveal a block's tokens by
    ``remasking`` before one more forward of the finished block writes
    its K/V (the commit). serve/step_programs.py ``_jit_decode_blocks``
    is the program, docs/serving.md "A model that decodes by blocks"
    the contract."""
    block_length: int
    mask_token_id: int
    denoising_steps: int
    remasking: str
    confidence_threshold: float

    def transfer_counts(self) -> Tuple[int, ...]:
        """Positions step s of a block reveals at the least (the
        source's ``get_num_transfer_tokens``): L // T each, the first
        L % T steps one more."""
        L, T = self.block_length, self.denoising_steps
        return tuple(L // T + (s < L % T) for s in range(T))

    def forwards(self, masked: int) -> int:
        """The MOST forwards a block that opens with ``masked`` masked
        positions costs, its commit among them; exactly that many under
        the two schedules that reveal a fixed count a step."""
        left, steps = masked, 0
        for n in self.transfer_counts():
            if left <= 0:
                break
            left, steps = left - n, steps + 1
        return steps + 1


REMASKING = ("sequential", "low_confidence_static",
             "low_confidence_dynamic")


def block_decode(cfg) -> Optional[BlockDecode]:
    """``cfg``'s ``BlockDecode`` where the model decodes by blocks, else
    None: serving asks the config, never its type."""
    return getattr(cfg, "block_decode", None)


def has_latent_pages(cfg) -> bool:
    return KIND_LATENT in layer_kinds(cfg)


def has_sliding_entries(cfg) -> bool:
    return KIND_SLIDING in layer_kinds(cfg)


# What a kind of request state CANNOT do yet, the other half of
# ``page_layout``'s table: for each kind, what its layers keep and, for
# every option that shares, rewinds, ships, re-codes or shards
# per-request state and cannot handle that, the reason. A recurrent
# state can be neither snapshotted at a page boundary nor rewound. A
# latent page is handed out and shared by page id as any other (so the
# prefix cache and speculative decoding, which deal in page ids and a
# page offset only, serve it), but its payload is one latent entry a
# token and not K and V a head. A ring's entries AGE (a position's key
# is overwritten a ring's length later). A kind's options stand in the
# order a deployment is asked them: the first one set is the one its
# refusal names (docs/serving.md says what would lift each).
KIND_REFUSALS = {
    KIND_KV: ("K/V pages", {}),
    KIND_RECURRENT: ("a recurrent state a slot instead of K/V pages", {
        "kv_migration": "a KV pull ships pages only, and the recurrent "
                        "state is not in its frames",
        "prefix_cache": "a cached prefix's pages are shared, but the "
                        "recurrent state after that prefix was never "
                        "snapshotted",
        "spec_len": "rejected drafts are rolled back by clamping a "
                    "page offset, and a recurrent state cannot be "
                    "rewound",
        "sharding": "no partition rules exist for the recurrent state "
                    "or the layer that keeps it",
    }),
    KIND_LATENT: ("latent pages instead of K/V pages", {
        "kv_dtype": "the int8 code keeps one absmax scale a (page, KV "
                    "head), and a latent entry has no heads: one scale "
                    "would span the compressed vector and the rope key "
                    "alike",
        "kv_migration": "a KV pull's frames carry K and V a head, and "
                        "no frame exists for a latent page",
        "sharding": "the pool shards over the KV-head axis, and the "
                    "one latent entry every head reads cannot be split "
                    "over it; no partition rules exist for the layer",
    }),
    KIND_INDEXED: ("latent pages and pages of index keys instead of K/V "
                   "pages", {
        "kv_dtype": "the int8 code keeps one absmax scale a (page, KV "
                    "head), and neither a latent entry nor an index key "
                    "has heads; an index key's rounding also moves which "
                    "entries a query attends, not only how",
        "kv_migration": "a KV pull's frames carry K and V a head, and "
                        "no frame exists for a latent page or for the "
                        "page of index keys that shares its id",
        "sharding": "the pool shards over the KV-head axis, and neither "
                    "the one latent entry nor the one index key every "
                    "head reads can be split over it; no partition "
                    "rules exist for the layer or its selector",
    }),
    KIND_SLIDING: ("a ring of their window's keys and values a slot "
                   "instead of K/V pages", {
        "prefix_cache": "a cached prefix's pages are shared, but the "
                        "sliding layers' entries for that prefix were "
                        "overwritten as the request that made them "
                        "went on: they are gone",
        "spec_len": "rejected drafts are rolled back by clamping a "
                    "page offset, and no rule says yet which ring "
                    "entries a rewound row may still read",
        "kv_dtype": "the int8 code keeps one absmax scale a (page, KV "
                    "head), and a ring has no pages: its entries would "
                    "stay in the model's type beside int8 pages",
        "kv_migration": "a KV pull ships pages only, and a slot's "
                        "rings are not in its frames",
        "sharding": "no partition rules exist for the rings or the "
                    "layer that keeps them",
    }),
    KIND_BORROWED: ("no pages of their own and read an earlier layer's "
                    "K/V pages", {
        "kv_dtype": "a layer that reads another layer's pages attends "
                    "them in the model's type, and nothing dequantises "
                    "an int8 page for a reader that is not its owner",
        "kv_migration": "a KV pull's frames are told apart by counting "
                        "the layers that have pages, and the readers of "
                        "a pulled page have not been held to it",
        "sharding": "no partition rules exist for a layer that reads "
                    "pages sharded for another layer's heads",
    }),
    KIND_STATELESS: ("no request state at all", {
        "sharding": "no partition rules exist for the layer or for the "
                    "activation it takes from an earlier one",
    }),
    # not a kind of layer: HOW THE MODEL DECODES (``block_decode``). Its
    # layers keep K/V pages as any other's; what a step is differs
    DECODES_BY_BLOCKS: (
        "it decodes by blocks (a step is a forward of a whole block of "
        "positions, and a block's tokens exist only at its commit)", {
            "spec_len": "drafts are verified under a causal mask, one "
                        "token a position, and a block's positions see "
                        "each other",
            "capture_logprobs": "a position's token is chosen at one of "
                                "several forwards of its block, and no "
                                "buffer carries the probability it was "
                                "chosen with to its commit",
            "kv_migration": "a KV pull ships pages only, and the block "
                            "a decode replica would resume (its tokens, "
                            "its mask flags, its step) is not in its "
                            "frames",
            "prefix_cache": "a page of whole blocks is shareable in "
                            "principle, but a request's last prompt "
                            "block is rewritten beside its masks until "
                            "the first commit, and no test holds a "
                            "shared page to that yet",
            "kv_dtype": "a block's positions are written once a forward "
                        "until its commit, and an int8 page's scale "
                        "only grows with what was written: the "
                        "committed entries would be coded at a scale "
                        "the masked drafts set",
            "sharding": "no partition rules exist for the model, and the "
                        "block state a slot rides the decode program "
                        "unsharded",
        }),
}


def _refusal(cfg, kind: str, option: str, value) -> ValueError:
    keeps, why = KIND_REFUSALS[kind]
    what = (keeps if kind == DECODES_BY_BLOCKS
            else f"it has layers that keep {keeps}")
    return ValueError(
        f"{option}={value!r} is not supported for "
        f"{type(cfg).__name__}: {what}; {why[option]}")


def refuse_unsupported(cfg, **asked) -> None:
    """ValueError for the first row of ``KIND_REFUSALS`` (in its order:
    the kinds of ``cfg``'s layers, then how the model decodes) that
    cannot handle an option of ``asked`` that is set, naming the option,
    what the layers keep (or how the model decodes) and why; a model of
    K/V pages only that yields a token a step passes whatever is
    asked."""
    rows = layer_kinds(cfg) + (
        (DECODES_BY_BLOCKS,) if block_decode(cfg) is not None else ())
    for kind, (_, why) in KIND_REFUSALS.items():
        if kind not in rows:
            continue
        for option in why:
            if asked.get(option):
                raise _refusal(cfg, kind, option, asked[option])


def sliding_ring_len(cfg, page_size: int, prefill_chunk: int) -> int:
    """Positions a sliding layer's ring keeps a slot: the window and
    one prefill chunk, rounded up to whole pages, and one page more.
    A chunk's queries then never lose a key they can still see to the
    chunk's own writes (position p overwrites p - length, which lies
    more than a window behind every query of a call that holds p),
    whatever the page or the chunk the engine was given. 0 for a
    model without such a layer."""
    if not has_sliding_entries(cfg):
        return 0
    span = cfg.sliding_window + prefill_chunk
    return -(-span // page_size) * page_size + page_size


# The minor axis of a TPU array is stored in tiles of 128 lanes.
_LANES = 128
# the kinds of layer that have no page
_NO_PAGES = (KIND_RECURRENT, KIND_SLIDING, KIND_BORROWED, KIND_STATELESS)


def latent_page_width(cfg) -> int:
    """Columns a latent page stores a token's entry in:
    ``cfg.latent_dim`` (576 for A.X-K1) rounded up to whole 128-lane
    tiles (640), the rest zeros. The chip pads a minor axis of 576 to
    640 in memory whatever is declared; declared as 576 the compiler's
    compact layout avoids that padding by making ``n_pages`` the minor
    axis, a pool no page can be gathered from, and every step program
    copies each layer's pool into the page-major layout and back
    (10 whole-pool copies a call at five layers: PERF.md section 6,
    PR 34). Declared as it is kept, no program copies it."""
    return -(-cfg.latent_dim // _LANES) * _LANES


def page_layout(cfg, kind: str, page_size: int, kv_dtype: str = "fp"):
    """What ONE physical page of a layer of ``kind`` is stored as: a
    (shape, dtype) a tensor, in storage order. The pool
    (``init_kv_pool``), its bytes (``kv_pool_page_bytes``) and a
    shipped page's frames (``page_cols_from_bytes``) all read it here.
    kv fp:   k, v           [Pg, KH, D] cfg.dtype
    kv int8: k, v, sk, sv   [Pg, KH, D] int8 and [KH] fp32 absmax
             (each behind ``kv_entries_per_layer``'s pass axis, if any)
    latent:  one tensor     [Pg, latent_page_width(cfg)] cfg.dtype
    indexed: that, and      [Pg, cfg.index_head_dim] cfg.dtype: a
             token's index key (models/deepseek_v32.py), in a pool of
             its own under the SAME page ids
    recurrent, sliding: none (they belong to a slot, not to a page)
    borrowed, stateless: none (they keep nothing)
    """
    if kind in _NO_PAGES:
        return ()
    quantized = check_kv_dtype(kv_dtype) == "int8"
    if kind in (KIND_LATENT, KIND_INDEXED):
        if quantized:
            raise _refusal(cfg, kind, "kv_dtype", kv_dtype)
        latent = ((page_size, latent_page_width(cfg)), jnp.dtype(cfg.dtype))
        if kind == KIND_LATENT:
            return (latent,)
        return (latent, ((page_size, cfg.index_head_dim),
                         jnp.dtype(cfg.dtype)))
    passes = kv_entries_per_layer(cfg)
    shape = passes + (page_size, kv_page_heads(cfg), cfg.head_dim)
    if quantized:
        scale = (passes + (kv_page_heads(cfg),), jnp.dtype(KV_SCALE_DTYPE))
        return ((shape, jnp.dtype(jnp.int8)),) * 2 + (scale,) * 2
    return ((shape, jnp.dtype(cfg.dtype)),) * 2


class RecurrentState(NamedTuple):
    """One recurrent layer's storage, carried between jitted steps as
    a paged layer's ``(pages_k, pages_v)`` is: row s is decode slot
    s's.

    state: [n_slots, *cfg.recurrent_state_shape] float32
    conv:  [n_slots, *cfg.recurrent_conv_shape] cfg.dtype
    """
    state: jnp.ndarray
    conv: jnp.ndarray


class RecurrentStateView(NamedTuple):
    """``RecurrentState`` as a layer sees it in one call of B rows.

    slots: [B] int32, the slot each row carries (a row that carries
           none names slot ``n_slots``: it reads zeros and its write
           is dropped), or None where row i IS slot i (a decode call).
    valid: [B, T] bool, the real positions: a row's first ones.
    """
    state: jnp.ndarray
    conv: jnp.ndarray
    slots: Optional[jnp.ndarray]
    valid: jnp.ndarray

    def take(self, pool):
        """The rows' entries of ``pool`` (``state`` or ``conv``)."""
        return _take(pool, self.slots)

    def put(self, pool, rows):
        """``pool`` with the rows' entries replaced."""
        rows = rows.astype(pool.dtype)
        if self.slots is None:
            return rows
        return pool.at[self.slots].set(rows, mode="drop")


def _take(pool, slots):
    """The rows' entries of a per-slot ``pool``: row i is slot i where
    ``slots`` is None, zeros for a row that names no slot."""
    if slots is None:
        return pool
    return pool.at[slots].get(mode="fill", fill_value=0)


def decay_log_init(key, shape, dtype):
    """A recurrent layer's ``A_log`` a head: exp(A_log) in [1, 16], as
    the gated delta-rule and Mamba-2 layers are initialised (uniform,
    then log)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class SlidingRing(NamedTuple):
    """One sliding-window layer's storage, carried between jitted steps
    as a paged layer's ``(pages_k, pages_v)`` is: row s is decode slot
    s's ring, and position p of its request lies at index p mod L.

    k, v: [n_slots, n_kv_heads, L, head_dim] cfg.dtype,
          L = ``sliding_ring_len``: head-major inside a slot, as the
          attention's two contractions read it (declared otherwise the
          chip's compiler copies every ring into this layout on every
          step: ops/paged_attention.py)

    Written and read where it lies by the layer's one call
    (ops/ring_window_attention.py): on one TPU a Pallas kernel takes
    both arrays aliased to its results; elsewhere the jax.numpy pair.
    """
    k: jnp.ndarray
    v: jnp.ndarray


class SlidingRingView(NamedTuple):
    """``SlidingRing`` as a layer sees it in one call of B rows;
    ``slots`` and ``valid`` as ``RecurrentStateView``'s."""
    k: jnp.ndarray
    v: jnp.ndarray
    slots: Optional[jnp.ndarray]
    valid: jnp.ndarray


class PagedKVLayer(NamedTuple):
    """Per-layer view of the paged KV pool handed to the attention
    module (a pytree: safe to carry through jit/scan).

    pages_k/pages_v: [n_pages, page_size, n_kv_heads, head_dim]
                     (page-major: a page is one contiguous slab). A
                     LATENT layer has ``pages_v`` None and ``pages_k``
                     [n_pages, page_size, latent_page_width]: its
                     values are a column prefix of the same entries.
                     An INDEXED layer has ``pages_index`` beside them.
    page_table:      [n_slots, max_pages] int32 — logical page p of
                     slot s lives in physical page ``page_table[s, p]``
    scales_k/scales_v: [n_pages, n_kv_heads] fp32 per-page absmax
                     scales when the pool is int8, else None. Optional
                     LAST so fp pytrees keep their PR 1–14 structure.
    pages_index:     [n_pages, page_size, index_head_dim], an indexed
                     layer's index keys: page p of it holds the index
                     keys of the tokens whose latent entries page p of
                     ``pages_k`` holds. None everywhere else (LAST, for
                     the scales' reason).
    """
    pages_k: jnp.ndarray
    pages_v: jnp.ndarray
    page_table: jnp.ndarray
    scales_k: Optional[jnp.ndarray] = None
    scales_v: Optional[jnp.ndarray] = None
    pages_index: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return self.pages_k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.scales_k is not None


def kv_layer_view(layer, page_table: jnp.ndarray, slots=None,
                  valid=None):
    """Wrap one engine layer entry — ``(pk, pv)`` fp,
    ``(pk, pv, sk, sv)`` int8, ``(pages,)`` latent, ``(pages,
    index_pages)`` indexed (told from K and V by a latent page having
    no head axis), a
    ``RecurrentState`` or a ``SlidingRing``, or the empty tuple of a
    layer that keeps nothing — as what its
    layer consumes: a PagedKVLayer over ``page_table``, or a
    RecurrentStateView or SlidingRingView of the rows' ``slots`` and
    real positions (``valid``: a function giving the [B, T] mask,
    which only a layer that keeps its entry by slot calls), or the
    empty tuple as it is (a borrowed layer is handed its OWNER's
    PagedKVLayer by the model's own layer loop, after the owner's
    append: models/llama.py ``transformer_forward``'s ``published``).
    Keeps the jitted engine builders kind- and dtype-agnostic: they
    thread opaque entries and only this view/store pair knows what
    they are."""
    if isinstance(layer, RecurrentState):
        return RecurrentStateView(layer.state, layer.conv, slots, valid())
    if not layer:
        return ()
    if isinstance(layer, SlidingRing):
        return SlidingRingView(layer.k, layer.v, slots, valid())
    if len(layer) == 1:
        return PagedKVLayer(layer[0], None, page_table)
    if len(layer) == 2 and layer[0].ndim == 3:
        return PagedKVLayer(layer[0], None, page_table,
                            pages_index=layer[1])
    if len(layer) == 2:
        pk, pv = layer
        return PagedKVLayer(pk, pv, page_table)
    pk, pv, sk, sv = layer
    return PagedKVLayer(pk, pv, page_table, sk, sv)


def live_rows(kv_cache):
    """[B] bool, the rows of a paged call that carry a request, as
    their layer's view shows it: a paged layer's row whose page-table
    row is not the null row, a recurrent or sliding layer's row whose
    first position is real. None without a paged cache (every row is
    live). A mixture gives the other rows no expert."""
    if isinstance(kv_cache, PagedKVLayer):
        return kv_cache.page_table[:, 0] != 0
    if isinstance(kv_cache, (RecurrentStateView, SlidingRingView)):
        return kv_cache.valid[:, 0]
    return None


def kv_layer_store(cache: PagedKVLayer):
    """Inverse of kv_layer_view: the storage entry (without the
    call's page table, slots and valid positions) the engine carries
    between jitted steps."""
    if isinstance(cache, RecurrentStateView):
        return RecurrentState(cache.state, cache.conv)
    if not cache:
        return ()
    if isinstance(cache, SlidingRingView):
        return SlidingRing(cache.k, cache.v)
    if cache.pages_index is not None:
        return (cache.pages_k, cache.pages_index)
    if cache.pages_v is None:
        return (cache.pages_k,)
    if cache.scales_k is None:
        return (cache.pages_k, cache.pages_v)
    return (cache.pages_k, cache.pages_v,
            cache.scales_k, cache.scales_v)


def init_kv_pool(cfg, n_pages: int, page_size: int,
                 kv_dtype: str = "fp", n_slots: int = 0,
                 ring_len: int = 0, mark=None):
    """One entry per layer, by ``layer_kinds(cfg)``. Page 0 of a paged
    layer is reserved (null). ``mark``, if given, is told after each
    layer what was made: "pool" (pages) or "state" (a slot's own).

    fp:   (pages_k, pages_v) in cfg.dtype, each
          [n_pages, page_size, n_kv_heads, head_dim].
    int8: (pages_k, pages_v, scales_k, scales_v) — int8 pages
          plus fp32 per-(page, head) absmax scales initialised to 0
          (a 0 scale means "page holds nothing"; paged_append's
          reset-on-offset-0 rule keeps that true across realloc
          without any host-side scale bookkeeping).
    latent: (pages,) in cfg.dtype,
          [n_pages, page_size, latent_page_width(cfg)].
    indexed: (pages, index_pages): those and
          [n_pages, page_size, cfg.index_head_dim].
    recurrent: RecurrentState of ``n_slots`` rows, zeros.
    sliding: SlidingRing of ``n_slots`` rings of ``ring_len``
          (``sliding_ring_len``) positions, zeros: the same bytes
          whatever ``n_pages``.
    borrowed, stateless: () (nothing is made).
    """
    def entry(kind):
        if kind == KIND_SLIDING:
            shape = (n_slots, cfg.n_kv_heads, ring_len, cfg.head_dim)
            # two arrays: the pool is donated, one buffer cannot be twice
            return SlidingRing(jnp.zeros(shape, cfg.dtype),
                               jnp.zeros(shape, cfg.dtype))
        if kind == KIND_RECURRENT:
            return RecurrentState(
                jnp.zeros((n_slots,) + tuple(cfg.recurrent_state_shape),
                          jnp.float32),
                jnp.zeros((n_slots,) + tuple(cfg.recurrent_conv_shape),
                          cfg.dtype))
        return tuple(jnp.zeros((n_pages,) + shape, dtype) for shape, dtype
                     in page_layout(cfg, kind, page_size, kv_dtype))

    pool = []
    for kind in layer_kinds(cfg):
        pool.append(entry(kind))
        if mark is not None:
            mark("state" if kind in (KIND_RECURRENT, KIND_SLIDING)
                 else "pool")
    return pool


def kv_pool_page_bytes(cfg, page_size: int,
                       kv_dtype: str = "fp") -> int:
    """Bytes ONE physical page costs across the layers that HAVE pages
    (a K/V layer's k+v payload plus, for int8, its two fp32 scales; a
    latent layer's one entry a token, and an indexed layer's index
    key beside it). The allocator multiplies this
    by occupancy for the bytes view in load/leak reports — the number
    the capacity A/B halves."""
    return sum(int(np.prod(shape)) * dtype.itemsize
               for kind in layer_kinds(cfg)
               for shape, dtype in page_layout(cfg, kind, page_size,
                                               kv_dtype))


def sliding_bytes_per_slot(cfg, ring_len: int) -> int:
    """Bytes ONE decode slot's rings cost across the sliding layers (0
    for a model with none): k and v of ``ring_len`` positions a layer,
    whatever the slot's context."""
    if not ring_len:
        return 0
    return (layer_kinds(cfg).count(KIND_SLIDING) * 2 * ring_len
            * cfg.n_kv_heads * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


def state_bytes_per_slot(cfg, ring_len: int = 0) -> int:
    """Bytes ONE decode slot holds in the layers that keep their entry
    by slot and not by page (0 for a model with none): a recurrent
    layer's float32 state and its convolution tail in cfg.dtype, a
    sliding layer's ring (``sliding_bytes_per_slot``)."""
    total = sliding_bytes_per_slot(cfg, ring_len)
    n = layer_kinds(cfg).count(KIND_RECURRENT)
    if n:
        total += n * (4 * int(np.prod(cfg.recurrent_state_shape))
                      + jnp.dtype(cfg.dtype).itemsize
                      * int(np.prod(cfg.recurrent_conv_shape)))
    return total


def export_page_bytes(layers, page: int) -> List[List[bytes]]:
    """Raw bytes of ONE physical page across every layer that has
    pages (the engine exports nothing for a model with recurrent
    state; a layer that keeps nothing, the empty tuple, ships nothing:
    a borrowed layer's keys travel as its owner's) — the unit a cross-replica KV pull ships. Each entry is the layer's tensor
    tuple serialized in storage order: ``[k, v]`` for fp pools,
    ``[k, v, sk, sv]`` for int8 (the per-page scales TRAVEL WITH the
    payload — a page without its scale is garbage). ``t[page]`` is
    the page as it lies in the pool, so k/v blobs are ``[Pg, KH, D]``
    and scale blobs ``[KH]``; blocks until any in-flight device
    computation producing ``layers`` has settled."""
    return [[np.asarray(t[page]).tobytes() for t in layer]
            for layer in layers if len(layer)]


def page_cols_from_bytes(cfg, page_size: int, kv_dtype: str,
                         blobs: Sequence[Sequence[bytes]]):
    """Inverse of ``export_page_bytes``: rebuild one page's per-layer
    arrays from raw bytes, shaped for a
    ``pages.at[dst].set(col)`` landing — k/v ``[Pg, KH, D]``,
    scales ``[KH]`` (``page_layout`` a layer). Validates arity and
    byte counts so a truncated or cross-dtype blob fails typed instead
    of landing garbage KV."""
    layouts = [layout for layout in (
        page_layout(cfg, kind, page_size, kv_dtype)
        for kind in layer_kinds(cfg)) if layout]
    if len(blobs) != len(layouts):
        raise ValueError(
            f"page payload has {len(blobs)} layers, pool has "
            f"{len(layouts)}")
    out = []
    for li, (layer_blobs, layout) in enumerate(zip(blobs, layouts)):
        if len(layer_blobs) != len(layout):
            raise ValueError(
                f"layer {li}: {len(layer_blobs)} tensors, "
                f"{kv_dtype} pool stores {len(layout)}")
        cols = []
        for b, (sh, dt) in zip(layer_blobs, layout):
            want = int(np.prod(sh)) * dt.itemsize
            if len(b) != want:
                raise ValueError(
                    f"layer {li}: {len(b)}-byte tensor, expected "
                    f"{want} for shape {sh} {dt.name}")
            cols.append(np.frombuffer(b, dtype=dt).reshape(sh))
        out.append(tuple(cols))
    return out


class BlockAllocator:
    """Host-side free-list allocator over the physical page pool.

    Page 0 is never handed out — it is the null page inactive slots
    write into. All-or-nothing alloc so a half-grown sequence never
    holds pages it cannot use.

    ``page_bytes`` (optional) is the all-layer byte cost of one page
    (see kv_pool_page_bytes); when set, occupancy gains a bytes view
    so pool_stats/load_report/flight bundles show the memory the
    dtype choice actually buys back.
    """

    def __init__(self, n_pages: int, page_bytes: Optional[int] = None):
        if n_pages < 2:
            raise ValueError("pool needs >= 2 pages (page 0 is null)")
        self.n_pages = n_pages
        self.page_bytes = page_bytes
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._free_set = set(self._free)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def occupancy(self) -> int:
        """Pages currently handed out (the null page never counts).
        At engine quiescence this must equal the prefix cache's
        resident page count — every other page is a leak."""
        return (self.n_pages - 1) - len(self._free)

    def bytes_in_use(self) -> Optional[int]:
        """occupancy() in bytes, or None when page_bytes is unknown."""
        if self.page_bytes is None:
            return None
        return self.occupancy() * self.page_bytes

    def bytes_total(self) -> Optional[int]:
        """Whole-pool byte budget (null page included — it is real
        memory), or None when page_bytes is unknown."""
        if self.page_bytes is None:
            return None
        return self.n_pages * self.page_bytes

    def leak_report(self) -> List[int]:
        """Page ids some owner still holds (not on the free list).
        Diff this against the set of legitimately-held pages (e.g.
        the prefix cache's nodes) to name leaked pages in test
        failures instead of just counting them."""
        return [p for p in range(1, self.n_pages)
                if p not in self._free_set]

    def alloc(self, n: int) -> Optional[List[int]]:
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        return out

    def free(self, pages: Sequence[int]) -> None:
        """Return pages to the free list. Rejects — atomically, before
        any page is accepted — frees of the null page (0), ids outside
        the pool, pages already free (double free), and the same page
        listed twice in one call. Silent acceptance of any of these
        corrupts the pool: the page would later be handed to two
        sequences whose KV scatters then overwrite each other — and
        once the prefix cache shares refcounted pages across
        sequences, a stray free is a cross-REQUEST corruption, not
        just a self-corruption."""
        seen = set()
        for p in pages:
            if not isinstance(p, (int, np.integer)):
                raise ValueError(f"page id {p!r} is not an int")
            if not 0 < p < self.n_pages:
                raise ValueError(
                    f"bad page id {p} (null page 0 and ids >= "
                    f"{self.n_pages} are never freeable)")
            if p in self._free_set:
                raise ValueError(f"double free of page {p}")
            if p in seen:
                raise ValueError(
                    f"page {p} listed twice in one free() call")
            seen.add(p)
        self._free.extend(pages)
        self._free_set.update(pages)


def kv_entries_per_layer(cfg) -> Tuple[int, ...]:
    """The pass axis of a K/V layer's page, as a shape prefix: () for a
    model whose layers run once a token (one cache entry a layer, the
    pool it always had), ``(T,)`` for one whose config declares
    ``kv_entries_per_layer`` = T (models/ouro.py: the one stack of
    layers runs T times, and a token keeps T entries a layer of
    weights). The number of cache entries is the CACHE's, not the
    weights': ``layer_kinds`` stays ``n_layers`` long and the page
    carries the factor, so a page id still names one page of every
    entry. (Defined at the file's end, and ``page_layout`` kept to its
    lines: the step programs' compile-cache key carries the line of
    every function above that makes an operation, PERF.md section 7.)"""
    passes = getattr(cfg, "kv_entries_per_layer", None)
    return () if passes is None else (int(passes),)


def kv_page_heads(cfg) -> int:
    """Head rows a K/V page stores a token: ``cfg.n_kv_heads``, or the
    config's own ``kv_page_heads`` where it declares more (models/
    olmo_hybrid.py: 30 K/V heads stored as 32, the last two zeros). A
    page's two minor axes are (heads, head_dim), and the chip tiles a
    bfloat16 array's in 16 x 128: with 30 heads it pads every token's
    entry to 32 in memory whatever is declared, and the compiler's way
    around that padding is a pool with the page's POSITIONS second-minor
    ({3,1,2,0}), which no page can be appended to or gathered from as it
    lies: every step program then copies every layer's K and V pool on
    entry and back on exit (16 whole-pool copies a dispatch and a 3.4 GB
    temporary at Olmo-Hybrid's widths, compiled for a described v5e:
    PERF.md section 6, PR 49; ``latent_page_width``'s lesson for a head
    axis). Declared as it is kept, no program copies it. Whole query
    heads ride the padding (the layer pads q, k and v alike), so the
    decode kernel's rule of whole sublane tiles holds too. (Defined at
    the file's end for ``kv_entries_per_layer``'s reason.)"""
    return int(getattr(cfg, "kv_page_heads", None) or cfg.n_kv_heads)


def kv_query_heads(cfg, kind: str) -> int:
    """Query heads a layer of ``kind`` hands its attention:
    ``cfg.n_heads``, or the config's own ``query_heads_by_kind`` where
    its layer types differ in their query over one K/V pool (models/
    laguna.py: 48 heads in a full layer, 64 in a sliding one, over 8
    K/V heads in both). What the engine builds a decode step's query
    from when it asks a kind's kernel whether it serves this model, so
    that ``decode_kernel_pages`` and ``sliding_kernel_keys`` answer for
    the shape the layer really hands it. (Defined at the file's end for
    ``kv_entries_per_layer``'s reason.)"""
    by_kind = getattr(cfg, "query_heads_by_kind", None) or {}
    return int(by_kind.get(kind, cfg.n_heads))


def sampled_only_from(cfg) -> int:
    """The index of the first layer of the model's TRAILING run of
    layers that keep no entry (``KIND_BORROWED``, ``KIND_STATELESS``):
    from there on a call that samples one position a row (models/
    llama.py ``transformer_forward``'s ``logits_at``) computes that
    position alone. ``n_layers`` where the last layer keeps an entry:
    every family but Phi-4-mini-flash (18 of 32: the cross-decoder, the
    paper's linear-time prefill).

    Why that is sound for ANY such layer, whatever the model. A layer
    that keeps no entry must give the same result in a decode step as
    in a prefill call. In a decode step (``T = 1``) it can see another
    position only through pages it borrows, and its own position only
    through its input or through what an earlier layer of the same call
    published there: so, given what it reads, it is position-wise, and
    its result at a position is the same whether or not the call
    computes it at the call's other positions. And since it writes
    nothing, no later call can tell whether it ran at a position at
    all. A layer that keeps an entry is another matter (its entry at
    EVERY position is read later), which is why only the trailing run
    counts: after its first layer nothing of the call's other positions
    is ever read again but through an owner's pages, which the owner
    has appended in full by then. (Defined at the file's end for
    ``kv_entries_per_layer``'s reason.)"""
    kinds = layer_kinds(cfg)
    first = len(kinds)
    while first and kinds[first - 1] in (KIND_BORROWED, KIND_STATELESS):
        first -= 1
    return first
