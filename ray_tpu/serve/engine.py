"""Continuous-batching LLM engine with a paged KV cache.

Iteration-level scheduling (the vLLM idea, built TPU-first): requests
join and leave the decode batch at token granularity instead of
decode-to-completion batches. Supersedes the coalescing batch queue
for LLM serving (ref: python/ray/serve/batching.py:46,215 — which can
only batch whole calls; a long completion there blocks every rider).

TPU/XLA design:
- ONE jitted decode step, compiled once, processes a fixed set of
  ``max_slots`` decode slots every iteration (static shapes). Inactive
  slots point at the null page (page 0) and their outputs are ignored
  host-side — no lax.cond, no divergence, no retrace.
- KV lives in a paged pool (models/kv_cache.py): the host-side
  BlockAllocator hands pages to sequences as they grow; completion or
  preemption returns them. Memory is bounded by the pool, not by
  max_slots x max_len.
- Decode is DEVICE-PACED: per-slot next-token and write position live
  on device and chain dispatch-to-dispatch; admission seeds slot rows
  with an on-stream scatter; token readbacks trail asynchronously and
  only ever block on a dispatch older than the newest one. With a
  full batch the scheduler runs ahead to the next completion event
  (dispatch-time arithmetic when no eos is configured), so the host
  syncs exactly when a scheduling decision is possible — host round
  trips never gate the token rate.
  Join/leave granularity under load is ``chunk`` tokens.
- Prefill is CHUNKED and interleaved with decode: each prompt
  advances by at most ``prefill_chunk`` tokens per scheduling round,
  in a row of its own of ONE prefill call that is
  ``_max_prefill_batch`` rows wide whatever rides in it — so a round
  grants up to ``_max_prefill_batch`` mid-prefill slots a chunk each
  (the round's budget is rows x chunk: what the call costs), and
  every round dispatches the prefill chunk immediately followed by a
  short decode chunk, so in-flight decode never stalls for a whole
  prompt the way monolithic padded-batch prefill stalls it.
  Admission only needs pages for the FIRST chunk (chunk-budget
  admission), later chunks grow pages like decode does. A request's
  first token is sampled by the chunk that consumes the END of its
  prompt and is emitted to the stream right then — TTFT is one
  prompt-prefill, not prompt-prefill plus a decode-chunk drain. The
  round planner itself is pure and device-free (serve/scheduler.py)
  so CPU tests drive it deterministically.
- Preemption is recompute-based: when the pool runs dry the youngest
  slot is evicted, its pages freed, and the request requeued with
  prompt = original prompt + tokens generated so far, so clients see
  an uninterrupted stream.
- Pool pages are DONATED to each jitted call, so XLA updates them in
  place — decode does not copy the cache every step.

Works for every Llama-shaped family (Llama, Mixtral) since they share
LlamaAttention via block_forward, and for a hybrid whose layers keep
TWO KINDS of per-request state (models/solar_open2.py): K/V pages in
some layers, a fixed-size recurrent state a SLOT in the others
(models/kv_cache.py ``RecurrentState``). The step programs thread
both through the same donated pool; the host hands a prefill call its
rows' slot ids and keeps no other book on the state: a row whose
start offset is 0 begins from zeros inside the program, so
admission, preemption-recompute and fault-requeue need nothing new,
and slots that carry no request ride a decode call without moving
theirs. What a recurrent state cannot do yet is be snapshotted or
rewound, so the engine refuses ``prefix_cache``, ``spec_len > 0``,
KV export/pull and tensor/expert sharding for such a model
(models/kv_cache.py ``refuse_unsupported``, the one table of what a
kind of state cannot do; docs/serving.md says what would lift each).
A model of LATENT attention (models/axk1.py) has pages of a third
kind: one pool a layer of one latent entry a token, handed
out, shared and rewound by page id and offset exactly as K/V pages
are; what interprets, ships or shards a page's payload (int8 pages,
KV export/pull, tensor sharding) is refused for it (the same table's
latent row). A model of recurrent and latent layers ONLY
(models/kimi_linear.py: no K/V layer at all) is served by the same
pool and stands on both rows at once. There a slot costs a
fixed ``state_bytes_per_slot`` whatever its context and a token only a
latent entry in the few layers that have pages, so the deployment is
sized slots first: ``max_slots`` by the state's bytes (and by the
decode step, which moves every slot's state), ``n_pages`` then so
generously that pages never bound the slots (docs/serving.md, "Sizing
``max_slots`` and ``n_pages`` when state, not pages, bounds the
slots"). A model whose layers keep caches of TWO SIZES
(models/mellum.py: a sliding window in three layers of four) has K/V
pages in its full layers, byte for byte the other K/V models', and in
its sliding layers a RING a slot (models/kv_cache.py ``SlidingRing``):
the last ``sliding_ring_len`` positions' keys and values, kept and
counted as a recurrent state is (by slot, in ``state_bytes_per_slot``,
never cleared by the host), so a context eight times the window costs
those layers what the window does. Entries that age can be neither
shared by prefix nor shipped, and what rewinds, quantises or shards
them is not written: the table's sliding row.

A model that DECODES BY BLOCKS (models/sdar.py; ``cfg.block_decode``,
models/kv_cache.py ``BlockDecode``: the engine asks the config, never
its type) keeps K/V pages as any other, but a decode STEP of it is a
forward of every slot's whole block and yields no token or a block's
(serve/step_programs.py ``_jit_decode_blocks``, built in
``_jit_decode``'s place). The host's books are then in FORWARDS and
BLOCKS: a prefill call covers the prompt's whole blocks and emits
nothing (the remainder opens the first generated block beside masks:
``_open_blocks_locked``, ``_seed_blocks_locked``); ``_owed`` is a BOUND
in forwards, ``blocks left x (denoising steps + 1)``; ``slot.pos`` is a
bound on the block's start, used to grow pages before a dispatch and
put right at readback; a committed block's tokens reach the client
together (TTFT is the first commit); a slot retires when the readback
shows its last block or the bound is consumed, whichever is first, and
meanwhile the device idles it. docs/serving.md, "A model that decodes
by blocks".
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ray_tpu.models.kv_cache import (BlockAllocator, block_decode,
                                     check_kv_dtype,
                                     export_page_bytes, init_kv_pool,
                                     kv_pool_page_bytes,
                                     page_cols_from_bytes,
                                     refuse_unsupported,
                                     sliding_bytes_per_slot,
                                     sliding_ring_len,
                                     state_bytes_per_slot)
from ray_tpu.serve import kv_migration, obs, spec_decode
# Typed lifecycle errors live in a jax-free module (serve/errors.py)
# so the HTTP proxy and clients can import them without the device
# stack; RequestError is re-exported here for existing call sites.
from ray_tpu.serve.errors import (DeadlineExceeded, EngineDraining,
                                  EngineOverloaded, EngineShutdown,
                                  RequestCancelled, RequestError)
from ray_tpu.serve.faults import EngineFault
from ray_tpu.serve.prefix_cache import PrefixCache
from ray_tpu.serve.round_accounts import RoundAccounts
from ray_tpu.serve.scheduler import (LANE_BATCH, LANE_ONLINE,
                                     REPLICA_ROLES, ROLE_UNIFIED,
                                     StepPlan, SlotView, plan_step,
                                     role_plan_caps)
from ray_tpu.serve.step_programs import (_jit_copy_page, _jit_decode,
                                         _jit_decode_blocks,
                                         _jit_prefill, _jit_seed,
                                         _jit_seed_blocks, _jit_verify,
                                         _jit_write_page, block_state)
from ray_tpu.util.compile_cache import (build_log, metadata_keyed,
                                        summarize_builds)

_DONE = object()

SHED_TOTAL = "serve_engine_shed_total"
CANCELLED_TOTAL = "serve_engine_cancelled_total"
DEADLINE_TOTAL = "serve_engine_deadline_exceeded_total"
CONTAINED_TOTAL = "serve_engine_contained_faults_total"
RETRIES_TOTAL = "serve_engine_retries_total"
BATCH_TOKENS_TOTAL = "serve_batch_tokens_total"
BATCH_PREEMPTED_TOTAL = "serve_batch_preempted_total"
WEIGHT_SWAP_TOTAL = "serve_weight_swap_total"
WEIGHT_ROLLBACK_TOTAL = "serve_weight_rollback_total"

_METRICS: Optional[dict] = None


def _metrics() -> dict:
    """Lazy module-level lifecycle metric singletons, re-created if a
    test's ``clear_registry()`` dropped them (same pattern as
    serve/prefix_cache.py)."""
    global _METRICS
    from ray_tpu.util import metrics
    if (_METRICS is None
            or metrics.registry().get(SHED_TOTAL)
            is not _METRICS["shed"]):
        _METRICS = {
            "shed": metrics.Counter(
                SHED_TOTAL, "Requests rejected at submit because the "
                "admission queue was at max_queued"),
            "cancelled": metrics.Counter(
                CANCELLED_TOTAL,
                "Requests aborted by the client (cancel/disconnect)"),
            "deadline_exceeded": metrics.Counter(
                DEADLINE_TOTAL,
                "Requests expired by their per-request deadline"),
            "contained_faults": metrics.Counter(
                CONTAINED_TOTAL, "Dispatch/readback faults contained "
                "to one request instead of failing the engine"),
            "retries": metrics.Counter(
                RETRIES_TOTAL, "Innocent requests requeued after a "
                "contained fault (bounded retry policy)"),
            "batch_tokens": metrics.Counter(
                BATCH_TOKENS_TOTAL, "Tokens emitted to BATCH-lane "
                "requests (the capacity the batch tier absorbed)"),
            "batch_preempted": metrics.Counter(
                BATCH_PREEMPTED_TOTAL, "BATCH-lane slots preempted "
                "— yielded to online traffic or page pressure; the "
                "request requeues and recomputes/prefix-resumes"),
            "weight_swaps": metrics.Counter(
                WEIGHT_SWAP_TOTAL, "In-place hot weight swaps "
                "applied (monotonic generation-fence flips between "
                "scheduler rounds)"),
            "weight_rollbacks": metrics.Counter(
                WEIGHT_ROLLBACK_TOTAL, "Fleet rollout rollbacks: a "
                "canaried generation failed its health/parity gates "
                "and the controller re-installed the old payload "
                "under a fresh generation"),
        }
    return _METRICS


WEIGHT_GENERATION_GAUGE = "serve_weight_generation"

_WEIGHT_GEN_GAUGE = None


def _weight_generation_gauge():
    """Lazy singleton for the per-replica weight-generation gauge
    (clear_registry()-proof, same pattern as _metrics())."""
    global _WEIGHT_GEN_GAUGE
    from ray_tpu.util import metrics
    if (_WEIGHT_GEN_GAUGE is None
            or metrics.registry().get(WEIGHT_GENERATION_GAUGE)
            is not _WEIGHT_GEN_GAUGE):
        _WEIGHT_GEN_GAUGE = metrics.Gauge(
            WEIGHT_GENERATION_GAUGE,
            "Weight generation currently serving on each replica "
            "(the monotonic swap fence; rollback still advances it "
            "— weights_id names the payload)",
            tag_keys=("replica",))
    return _WEIGHT_GEN_GAUGE


KV_BYTES_TOTAL = "serve_kv_bytes_total"

_KV_GAUGE = None


def _kv_bytes_gauge():
    """Lazy singleton for the KV byte-budget gauge (same
    clear_registry()-proof pattern as _metrics()). Tagged by kv_dtype
    so an fp/int8 A/B in one process exposes both samples."""
    global _KV_GAUGE
    from ray_tpu.util import metrics
    if (_KV_GAUGE is None
            or metrics.registry().get(KV_BYTES_TOTAL) is not _KV_GAUGE):
        _KV_GAUGE = metrics.Gauge(
            KV_BYTES_TOTAL,
            "Paged KV pool byte budget (all layers, incl. scales)",
            tag_keys=("kv_dtype",))
    return _KV_GAUGE


def _dev_ready(buf) -> bool:
    """True when a device array's computation has finished (readback
    would not block). Conservative False when the runtime can't say."""
    try:
        return bool(buf.is_ready())
    except Exception:
        return False


def _first_leaf(buf):
    """Representative device array of a readback entry. Logprob
    capture packs (tokens, logprobs) pairs out of one jitted call, so
    either leaf's readiness stands for the pair's."""
    return buf[0] if isinstance(buf, tuple) else buf


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: List[int]            # original prompt (never mutated)
    max_new_tokens: int
    out_q: "queue.Queue[Any]" = dataclasses.field(
        default_factory=queue.Queue)
    generated: List[int] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    error: Optional[BaseException] = None
    closed: bool = False         # _DONE delivered; drop late tokens
    cancel_error: Optional[BaseException] = None
                                 # cancel requested (set WITHOUT the
                                 # engine lock): the scheduler's next
                                 # round tears the request down even
                                 # if the canceller never wins the lock
    t_submit: float = 0.0        # monotonic clock at submit()
    t_first: Optional[float] = None   # first token EMITTED to stream
    deadline: Optional[float] = None  # absolute monotonic deadline
    attempts: int = 0            # requeues after contained faults
    t_earliest: float = 0.0      # retry backoff: no re-admission
                                 # before this monotonic instant
    trace_id: Optional[str] = None    # request-scope trace id (minted
                                 # at the HTTP proxy, survives pool
                                 # resubmits)
    t_last_emit: Optional[float] = None   # last stream emission (for
                                 # the inter-token phase histogram)
    pull: Optional[Dict[str, Any]] = None  # cross-replica KV pull
                                 # hint from the router: {"hashes":
                                 # [...], ...opaque fetcher fields}.
                                 # Consumed EXACTLY ONCE at first
                                 # admission — cleared before the
                                 # pull starts, so a preemption or
                                 # fault requeue can never re-pull.
    batch: bool = False          # BATCH lane (priority="batch",
                                 # serve/batch_tier.py): preemptible
                                 # offline work. Admits only behind
                                 # every waiting online request, is
                                 # the first preemption victim, and
                                 # counts in its own queue-depth lane
                                 # so the autoscaler never scales for
                                 # preemptible backlog.
    logprobs: Optional[List[float]] = None
                                 # per-token sampling logprobs, index-
                                 # aligned with ``generated`` (RL
                                 # rollout capture, ray_tpu/rl). None
                                 # unless the engine was built with
                                 # ``capture_logprobs=True``; appended
                                 # by _emit_to in the same truncation
                                 # loop as the tokens, so eos/budget
                                 # cuts and preemption recompute keep
                                 # the two lists aligned by
                                 # construction.
    reveal_steps: Optional[List[int]] = None
                                 # a model that decodes by blocks, with
                                 # ``LLMEngine.record_reveals`` set (a
                                 # test's seam): for each generated
                                 # token the forward of its block (0 =
                                 # the block's first) that revealed it,
                                 # index-aligned with ``generated``

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.generated)

    @property
    def recompute_prompt(self) -> List[int]:
        """What to prefill after a preemption: everything the client
        has already seen."""
        return self.prompt + self.generated


class RequestHandle:
    """Client-side view of a submitted request."""

    def __init__(self, req: _Request,
                 engine: Optional["LLMEngine"] = None):
        self._req = req
        self._engine = engine
        self._drained = False

    def cancel(self) -> bool:
        """Abort the request at whatever phase it is in — queued,
        mid-prefill, decoding, or mid-speculation. Its slot frees,
        its pages return to the allocator (shared prefix pages only
        drop their reference), and any ``stream()``/``result()``
        consumer unblocks with ``RequestCancelled``. Returns False
        when the request had already finished (tokens delivered or
        failed) — cancellation after completion is a no-op."""
        if self._engine is None:
            return False
        return self._engine._cancel(self._req)

    @property
    def done(self) -> bool:
        return self._req.closed

    @property
    def error(self) -> Optional[BaseException]:
        return self._req.error

    @property
    def weights_tag(self) -> Optional[str]:
        """``generation:weights_id`` of the serving engine at read
        time (the X-Model-Generation header value) — which weight
        payload a mid-rollout client was actually served by."""
        eng = self._engine
        if eng is None:
            return None
        gen = getattr(eng, "weight_generation", None)
        if gen is None:
            return None
        return f"{gen}:{getattr(eng, 'weights_id', None)}"

    def stream(self):
        """Yield generated token ids as they are produced."""
        while True:
            item = self._req.out_q.get()
            if item is _DONE:
                if self._req.error is not None:
                    raise self._req.error
                return
            yield item

    def result(self) -> List[int]:
        """Block until completion; return all generated token ids.
        Idempotent: once the stream has been drained (here or via
        ``stream()`` running to completion elsewhere), repeat calls
        return the cached tokens — or re-raise the terminal error —
        instead of blocking on an already-consumed queue."""
        if not self._drained:
            self._drained = True
            for _ in self.stream():
                pass
        if self._req.error is not None:
            raise self._req.error
        return list(self._req.generated)

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit-to-first-emission latency, stamped when the first
        token is PUT ON THE REQUEST STREAM (end of this request's
        prefill) — not when a decode chunk later drains. None until
        the first token is out."""
        if self._req.t_first is None:
            return None
        return self._req.t_first - self._req.t_submit

    @property
    def logprobs(self) -> Optional[List[float]]:
        """Per-token sampling logprobs, index-aligned with
        ``result()``: entry i is log p(token_i | prefix) under the
        weights that sampled it. None unless the engine was built
        with ``capture_logprobs=True``. Read after ``done`` (or
        ``result()``) for the complete, truncation-consistent list —
        mid-stream reads see a prefix."""
        lp = self._req.logprobs
        return None if lp is None else list(lp)


@dataclasses.dataclass
class _Slot:
    req: _Request
    pages: List[int]             # physical page ids, logical order
    pos: int                     # next KV write position (host mirror;
                                 # the device carries the live value)
    cur: Optional[int]           # None until the slot's seed scatter
                                 # is dispatched; afterwards a sentinel
                                 # — the next-token input lives ON
                                 # DEVICE (dev_cur), never read back
                                 # for dispatching
    admit_seq: int               # LIFO preemption order
    prompt: List[int] = dataclasses.field(default_factory=list)
                                 # recompute-prompt snapshot being
                                 # prefilled (chunk by chunk)
    prefilled: int = 0           # prompt tokens whose KV is in pages
    decoded: int = 0             # decode steps ridden (dispatch-time
                                 # arithmetic, ahead of emission)
    preempted: bool = False     # in-flight tokens must be discarded
    shared: int = 0              # leading pages owned by the prefix
                                 # cache (read-only: COW — scatters
                                 # may only target pages >= shared)
    spec: Optional[Any] = None   # per-slot n-gram proposer
                                 # (spec_decode.NGramIndex); dies with
                                 # the slot on preemption, rebuilt at
                                 # re-admission — no stale drafts
    spec_pending: List[int] = dataclasses.field(default_factory=list)
                                 # drafts proposed at plan time,
                                 # consumed by this round's verify
    pulling: bool = False        # PULLING phase: a background thread
                                 # is pulling this request's prefix
                                 # KV from a peer replica. The slot
                                 # holds NO pages and rides NO
                                 # dispatch; the planner skips it
                                 # (SlotView.pulling) and the pull's
                                 # completion requeues the request at
                                 # the queue front for normal
                                 # admission (local hit or plain
                                 # prefill fallback).
    # A model that decodes by blocks (``_open_blocks_locked`` sets
    # these at admission; None/0/empty for every other model):
    forwards: Optional[int] = None
                                 # the BOUND on the forwards this
                                 # admission's blocks cost, against
                                 # which ``decoded`` counts forwards
    tail: List[int] = dataclasses.field(default_factory=list)
                                 # the prompt's remainder past its last
                                 # whole block: it opens the first
                                 # generated block (``prompt`` holds the
                                 # whole blocks, which are prefilled)
    end: int = 0                 # the last block's end: no forward of
                                 # this request writes at or past it
    reveals: List[int] = dataclasses.field(default_factory=list)
                                 # ``record_reveals``: the bit masks of
                                 # the open block's forwards so far

    @property
    def prefill_remaining(self) -> int:
        return len(self.prompt) - self.prefilled


class LLMEngine:
    """Continuous-batching decode engine for one model replica.

    Parameters
    ----------
    model, params: a Llama-family flax module + params.
    max_slots: decode batch width (static; compile-time).
    page_size: tokens per KV page.
    n_pages: physical pages in the pool (page 0 reserved as null).
    chunk: decode steps per device dispatch (host-sync amortization).
    prefill_chunk: prompt tokens ONE slot may prefill per scheduling
        round: the width of a row of the round's prefill call. The
        call is four rows wide whatever rides in it, so a round
        grants up to four mid-prefill slots a chunk each (a budget
        of 4 x prefill_chunk tokens, at the one call's price).
        Prompts longer than this prefill over several rounds, in
        one row, with decode chunks interleaved between them, so a
        long arrival stalls neither in-flight streams nor the
        prompts behind it; smaller values tighten decode latency
        under prefill load, larger values finish prompts (and thus
        first tokens) in fewer rounds.
    prefix_cache: share KV pages of identical page-aligned prompt
        prefixes across requests (radix tree + refcounts + LRU
        eviction, serve/prefix_cache.py). Repeated system-prompt /
        few-shot prefixes then admit at near-zero prefill cost.
    spec_len: speculative decoding (serve/spec_decode.py) — up to
        this many prompt-lookup draft tokens per slot per round,
        verified by ONE batched multi-token forward pass through the
        paged ``T>=1`` branch; the longest argmax-matching draft
        prefix (plus one bonus token) is kept, rejections roll back
        by clamping the slot's KV offset. 0 (default) disables.
        Greedy-only: sampling (temperature > 0) would need
        distribution-preserving rejection sampling, so speculation
        is silently disabled then — the accepted stream must stay
        bit-identical to non-speculative decode. Spec rounds are
        host-synchronous (acceptance gates the next dispatch), so
        the engine drains readbacks every round like the eos path.
    spec_ngram: suffix n-gram order for the prompt-lookup proposer.
    spec_proposer: test seam — a zero-arg factory returning an
        object with the NGramIndex protocol (sync/propose), built
        once per admitted slot.
    max_queued: bounded admission — with more than this many
        requests already waiting, ``submit`` fails fast with
        ``EngineOverloaded`` (shed counter + 429 at the proxy)
        instead of queueing into silent TTFT collapse. None
        (default) keeps the queue unbounded. Counts ONLY the online
        lane: preemptible batch backlog lives under
        ``max_queued_batch``.
    max_queued_batch: the BATCH lane's own admission bound (None,
        default, = unbounded — the no-TTFT-SLO deep queue of the
        throughput profile; the batch driver bounds its own in-flight
        window instead, serve/batch_tier.py).
    max_retries: bounded retry policy for fault containment — an
        innocent request swept up in another request's dispatch
        fault is requeued (recompute, like preemption) at most this
        many times before it fails too.
    retry_backoff_s: base of the exponential re-admission backoff
        after a contained fault (``backoff * 2**(attempt-1)``).
    shed_retry_after_s: the Retry-After hint carried by
        ``EngineOverloaded`` (surfaced as the HTTP header).
    admit_timeout_s: bound on how long ``submit`` may wait for the
        scheduler lock before shedding typed ``EngineOverloaded``.
        None (default) blocks indefinitely; set it when a watchdog
        guards the engine so callers racing a WEDGED scheduler
        (serve/watchdog.py) shed-and-reroute instead of parking on
        a lock only teardown would release.
    batch_wait_timeout_s: how long an engine with NO live slot holds
        its first admission for the prefill call's rows to fill, as
        ``serve.batch`` flushes at ``max_batch_size`` or
        ``batch_wait_timeout_s`` (serve/batching.py): it admits when
        as many requests wait as the call has rows, or when the
        oldest has waited this long. The call computes all its rows
        whatever they hold, and rows that start a prompt together
        stay together (the call's window loop runs to its LONGEST
        row), so a pipeline whose clients start milliseconds apart
        gets one schedule and not whichever a race over the first
        round picks. 0 (default) admits at once; a request never
        waits behind a live slot.
    fault_injector: test-only seam (serve/faults.py FaultInjector);
        None in production — every site is then a no-op.
    overlap: overlapped hot loop (default on). Each round plans and
        dispatches round N+1 from the PREVIOUS round's token frontier
        while round N still executes on device — the pre-plan drain
        only reads buffers the device has already finished, so the
        host never blocks before planning even in eos mode.
        Completion detection moves to readback time: a slot may
        over-decode past a late-revealed eos by at most one decode
        chunk (the planner caps stale riders, serve/scheduler.py),
        emission truncates at the eos exactly as before, and the
        overshot KV frontier is reclaimed by the same
        clamp-and-reseed machinery spec-decode rollback uses.
        ``overlap=False`` restores the lockstep loop (full blocking
        drain before planning in eos/spec mode — the PR-10 latency
        profile).
    capture_logprobs: record the sampling logprob of every emitted
        token (RL rollout capture, ray_tpu/rl). The jitted decode and
        prefill steps compute ``log_softmax`` of the sampling logits
        and gather the chosen token's logprob into a float32 buffer
        that rides the existing trailing-readback path — no extra
        host syncs, no extra dispatches. Tokens and logprobs stay
        index-aligned through eos/budget truncation and preemption
        recompute because emission appends both in one loop. Read via
        ``RequestHandle.logprobs``. Speculative decoding is silently
        disabled under capture (the verify path emits tokens without
        per-token distributions — same auto-disable contract as
        temperature > 0). Off by default: serving pays nothing.
    kv_dtype: KV pool storage dtype. ``"fp"``/None stores cfg.dtype
        pages (exact). ``"int8"`` stores quantized pages with one
        fp32 absmax scale per (kv_head, physical page) — half the
        page bytes, so a fixed byte budget holds ~2x the pages/slots
        /prefix residency. Outputs are tolerance-equal to fp (greedy
        token agreement gated in tests; spec accept-rate unchanged
        within noise), NOT bit-equal: quantized bytes depend on
        write history (docs/serving.md). Anything else is a
        ``ValueError`` (models/kv_cache.py ``check_kv_dtype``).
    """

    def __init__(self, model, params, *, max_slots: int = 8,
                 page_size: int = 16, n_pages: int = 256,
                 chunk: int = 4, prefill_chunk: Optional[int] = None,
                 max_run_ahead: Optional[int] = None,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 prefix_cache: bool = False,
                 spec_len: int = 0, spec_ngram: int = 3,
                 spec_proposer=None,
                 max_queued: Optional[int] = None,
                 max_queued_batch: Optional[int] = None,
                 max_retries: int = 2,
                 retry_backoff_s: float = 0.02,
                 shed_retry_after_s: float = 1.0,
                 admit_timeout_s: Optional[float] = None,
                 batch_wait_timeout_s: float = 0.0,
                 sharding=None,
                 fault_injector=None,
                 flight_dir: Optional[str] = None,
                 overlap: bool = True,
                 kv_dtype: Optional[str] = None,
                 prefix_digest_max: int = 512,
                 role: str = ROLE_UNIFIED,
                 capture_logprobs: bool = False):
        from ray_tpu.util.compile_cache import enable_compile_cache
        # where this constructor's time goes: the ``engine_init`` event
        _clk = obs.PhaseClock()
        enable_compile_cache()
        self.model = model
        self.cfg = model.config
        # Tensor-parallel placement (serve/sharding.py
        # EngineSharding): weights go down per the family's partition
        # rules, the KV pool head-shards over the ``tensor`` axis, and
        # every host->device operand commits replicated via _h2d.
        # Everything below the placement layer is sharding-oblivious —
        # same planner, same jitted step structure, same page tables.
        refuse_unsupported(
            self.cfg, prefix_cache=prefix_cache, spec_len=spec_len,
            kv_dtype=kv_dtype == "int8" and kv_dtype,
            sharding=sharding is not None,
            capture_logprobs=capture_logprobs)
        # how the model decodes: None (a token a sequence a step), or
        # the config's BlockDecode (a step is a forward of a block)
        self._block = block_decode(self.cfg)
        self.record_reveals = False  # test seam: _Request.reveal_steps
        self._sharding = sharding
        self._mesh = sharding.mesh if sharding is not None else None
        if sharding is not None:
            params = sharding.shard_params(params)
        self.params = params
        _clk.mark("params")
        # Weight-generation fence (live rollout, serve/weight_rollout):
        # strictly monotonic — every ``swap_weights`` must advance it,
        # including rollbacks (which install the OLD payload under a
        # NEW generation). ``weights_id`` names the payload itself so
        # convergence proofs can tell "rolled forward" from "rolled
        # back" when the generation alone cannot. ``replica_tag`` is
        # stamped by the pool (like ``role``) so the per-replica
        # generation gauge is attributable.
        self.weight_generation = 0
        self.weights_id = "g0"
        self.replica_tag = "0"
        self._pending_swap: Optional[Dict[str, Any]] = None
        self.S = max_slots
        self.Pg = page_size
        self.K = chunk
        self.PC = max(1, int(prefill_chunk or 256))
        if self._block is not None and (
                page_size % self._block.block_length
                or self.PC % self._block.block_length):
            # a prefill chunk starts on a block's edge, and a block
            # lies within one page
            raise ValueError(
                f"page_size={page_size} and prefill_chunk={self.PC} must "
                f"be whole multiples of the model's block_length "
                f"{self._block.block_length}")
        self.temperature = temperature
        self.eos_id = eos_id
        # Run-ahead ceiling: one dispatch may decode up to this many
        # steps before a host sync (the token buffer is [KMAX, S]).
        # The throughput profile (scheduler.SCHEDULER_PROFILES) sets
        # it explicitly — batch decode tolerates longer syncs.
        self.KMAX = (max(chunk, 128) if max_run_ahead is None
                     else max(chunk, int(max_run_ahead)))
        # Page-table width: the most a slot can address, so cap it at
        # what the model can legally address rather than the whole
        # pool. The attention programs gather and attend only up to
        # the batch's longest live context, a block of tokens at a time
        # (ops/paged_attention.py _paged_window_attention); the width
        # bounds that window.
        self.max_pages = min(n_pages - 1,
                             -(-self.cfg.max_seq_len // page_size))
        # KV storage dtype: "fp" (cfg.dtype pages, PR 1-14 behavior)
        # or "int8" (quantized pages + per-page scales, half the page
        # bytes -> double the pages at a fixed byte budget).
        self.kv_dtype = check_kv_dtype(kv_dtype)
        # Disaggregation role (serve/scheduler.py REPLICA_ROLES):
        # selects the planner knob clamps via role_plan_caps and is
        # stamped into every load_report so routing, autoscaling, and
        # flight bundles all see the same topology. Mutable on
        # purpose — EnginePool stamps roles after construction so one
        # engine factory serves both pools.
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"unknown replica role {role!r}; expected one of "
                f"{sorted(REPLICA_ROLES)}")
        self.role = role
        self.page_bytes = kv_pool_page_bytes(self.cfg, page_size,
                                             self.kv_dtype)
        self.alloc = BlockAllocator(n_pages,
                                    page_bytes=self.page_bytes)
        # the state a SLOT holds in the layers that keep no pages
        # (0 bytes, and nothing below differs, for a model without)
        # (a sliding layer's ring among them: the window and one
        # prefill chunk in whole pages, whatever the context)
        self.ring_len = sliding_ring_len(self.cfg, page_size, self.PC)
        self.sliding_bytes_per_slot = sliding_bytes_per_slot(
            self.cfg, self.ring_len)
        self.state_bytes_per_slot = state_bytes_per_slot(self.cfg,
                                                         self.ring_len)
        self.pages = init_kv_pool(self.cfg, n_pages, page_size,
                                  self.kv_dtype, n_slots=max_slots,
                                  ring_len=self.ring_len, mark=_clk.mark)
        if sharding is not None:
            self.pages = sharding.place_kv_pool(self.pages)
        _clk.mark("pool")
        # capacity gauge: the whole-pool byte budget this engine holds
        # (per process — chaos/fleet runs sum across scrapes). Set
        # once; pools are static-shape for the engine's lifetime.
        _kv_bytes_gauge().set(float(n_pages * self.page_bytes),
                              tags={"kv_dtype": self.kv_dtype})
        # Radix-tree prefix KV cache (serve/prefix_cache.py): retired
        # prompts' full pages enter the tree instead of the free list;
        # admission matches the longest cached prefix and skips its
        # prefill. Refcounted + LRU-evicted, so it costs nothing under
        # memory pressure. Off by default: sharing only pays when
        # prompts actually share page-aligned prefixes.
        self.prefix_cache = (PrefixCache(self.alloc, page_size)
                             if prefix_cache else None)
        # The engine's jitted programs by their names in a device
        # trace (jit_<function>), with the executables each held when
        # this engine took it: step() counts what a round adds
        # (stats["programs_built"], the ``compile`` event) and says
        # what each build was from the process's build log
        # (util/compile_cache.py), read from where it last looked.
        self._programs: Dict[str, Any] = {}
        self._program_sizes: Dict[str, int] = {}
        self._builds = build_log()
        self._build_cursor = self._builds.total
        _clk.mark("metrics")
        self._copy_page_fn = (
            self._track_program(_jit_copy_page(self._mesh))
            if prefix_cache else None)
        _clk.mark("programs")
        # Fleet prefix-cache digest advertisement cap: load reports
        # ship at most this many path hashes, truncated prefix-closed
        # longest/hottest-first (PrefixCache.digest) so fleet routing
        # traffic stays bounded as the tree grows.
        self.prefix_digest_max = max(0, int(prefix_digest_max))
        # Cross-replica KV migration (serve/kv_migration.py). The
        # REQUESTER side: ``kv_fetcher`` is injected by the pool/agent
        # — a callable(pull_plan) -> payload dict or None — and a
        # request submitted with a ``pull`` hint admits in the PULLING
        # phase, overlapping the transfer with other slots' work. The
        # DONOR side is the kv_pin_prefix/kv_export_pages/
        # kv_release_pages trio a KVDonor drives. Stats mirror the
        # process counters per engine (bench artifacts, pool_stats).
        self.kv_fetcher: Optional[Any] = None
        self.kv_migration_stats = kv_migration.new_stats()
        self._write_page_fn = None   # built on first pulled landing
        # RL rollout logprob capture: must be fixed before the jitted
        # decode/prefill builders run (they close over it).
        self.capture_logprobs = bool(capture_logprobs)
        # Speculative decoding (serve/spec_decode.py): greedy-only —
        # verification accepts drafts against the argmax, so with
        # sampling it would skew the output distribution. Silently
        # off at temperature > 0 (docs/serving.md), and under logprob
        # capture (the verify emits accepted tokens without per-token
        # sampling distributions).
        if spec_len < 0:
            raise ValueError("spec_len must be >= 0")
        if spec_len and spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        self.spec_len = (spec_len if temperature <= 0.0
                         and not self.capture_logprobs else 0)
        self.spec_ngram = spec_ngram
        self._proposer_factory = (
            spec_proposer if spec_proposer is not None
            else (lambda: spec_decode.NGramIndex(spec_ngram)))
        self._verify_fn = None       # built on first spec dispatch
        self.slots: List[Optional[_Slot]] = [None] * max_slots
        self._wait: "collections.deque[_Request]" = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._rid = itertools.count()
        self._admit_seq = itertools.count()
        self._rng = self._h2d(jax.random.PRNGKey(seed))
        # trailing readbacks: [(buf_dev, [(ix, slot, take), ...], steps)]
        self._fetchq: "collections.deque" = collections.deque()
        # in-flight prefills: [(firsts_dev, [(ix, slot, row), ...])]
        self._pending_prefill: List = []
        # the counters' vectors of a model that counts on the device
        # (serve/round_accounts.py): [(vector_dev, is_decode)], read back
        # behind the tokens of the same dispatch, never waited for
        self._moe_pending: "collections.deque" = collections.deque()
        # Device-authoritative decode state: the next-token input and
        # write position per slot LIVE ON DEVICE and chain dispatch to
        # dispatch — no host readback sits on the decode critical
        # path. Admission seeds rows via a jitted scatter (no sync);
        # host readbacks trail for emission only.
        self._dev_cur = self._h2d(jnp.zeros((max_slots,), jnp.int32))
        self._dev_pos = self._h2d(jnp.zeros((max_slots,), jnp.int32))
        # a block program's state a slot, in their place
        # (serve/step_programs.py ``block_state``)
        self._dev_blocks = (
            None if self._block is None else jax.tree_util.tree_map(
                self._h2d, block_state(max_slots,
                                       self._block.block_length)))
        _clk.mark("decode_state")
        # Without an eos the schedule is fully deterministic: slots
        # retire by arithmetic at dispatch time and host syncs never
        # gate scheduling. With an eos, completions depend on sampled
        # tokens — the LOCKSTEP loop drains readbacks before planning
        # every round; the OVERLAPPED loop (default) plans from the
        # stale frontier instead and detects eos at readback time.
        self._deferred = eos_id is None
        self.overlap = bool(overlap)
        self._stopped = False
        self._draining = False
        # Progress heartbeat (watchdog signal, serve/watchdog.py):
        # touched lock-free at the top of every scheduling round, at
        # every dispatch completion, and at every readback drain — so
        # a long-but-moving prefill keeps it fresh while a wedged
        # dispatch (hung XLA call, stuck transfer) lets it go stale.
        # Plain float assignment: GIL-atomic, no lock required.
        self._hb = time.monotonic()
        # Zombie fence: set by force_kill(). A wedged step thread
        # that later wakes finds this and may neither commit tokens
        # (its requests are closed) nor publish pages into the prefix
        # cache (retire-path inserts divert to plain frees).
        self._force_killed = False
        self._thread: Optional[threading.Thread] = None
        self.stats: Dict[str, int] = collections.Counter()
        # what a round dispatched, counted and must have read, for its
        # ``round`` event and the stats
        self.accounts = RoundAccounts(
            self.cfg, self.stats, self.pages, slots=self.S,
            page_size=page_size, max_pages=self.max_pages,
            kv_dtype=self.kv_dtype, mesh=self._mesh)
        # Request-lifecycle knobs: bounded admission + bounded retry
        if max_queued is not None and max_queued < 0:
            raise ValueError("max_queued must be >= 0 or None")
        self.max_queued = max_queued
        if max_queued_batch is not None and max_queued_batch < 0:
            raise ValueError("max_queued_batch must be >= 0 or None")
        self.max_queued_batch = max_queued_batch
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = max(0.0, float(retry_backoff_s))
        self.shed_retry_after_s = float(shed_retry_after_s)
        if admit_timeout_s is not None and admit_timeout_s <= 0:
            raise ValueError("admit_timeout_s must be > 0 or None")
        self.admit_timeout_s = admit_timeout_s
        if batch_wait_timeout_s < 0:
            raise ValueError("batch_wait_timeout_s must be >= 0")
        self.batch_wait_timeout_s = float(batch_wait_timeout_s)
        self._injector = fault_injector
        self._round = 0              # scheduling-round counter (the
                                     # fault seam's deterministic clock)
        # rows of the round's prefill call (one jitted call, fixed
        # row count): each carries one mid-prefill slot's chunk
        self._max_prefill_batch = 4
        # Chunked prefill: ONE jitted function, which jit specializes
        # per pow2 chunk bucket (floor page_size, cap prefill_chunk) —
        # a handful of shapes total, vs the old one-per-prompt-length
        # cache whose misses were measured as multi-second p99 stalls.
        self._prefill_fn = self._track_program(_jit_prefill(
            self.model, self.temperature, self._max_prefill_batch,
            self.capture_logprobs, self._mesh))
        _clk.mark("programs")
        # Typed lifecycle event log (serve/obs.py): lock-free bounded
        # ring recording every request phase and scheduler action.
        # ``sched_trace`` stays as a compat view rendering the four
        # legacy dispatch-order tuple kinds.
        self.events = obs.EventLog(8192, name="engine")
        self.sched_trace = obs.SchedTraceView(self.events)
        # the collector's long passes become ``gc`` events here
        obs.watch_gc(self)
        _clk.mark("events")
        # Flight recorder sink: when set, EngineFault containment and
        # whole-engine failure dump a postmortem bundle here.
        self.flight_dir = flight_dir
        # submit->first-emission latencies (seconds), most recent
        self.ttfts_s: "collections.deque" = \
            collections.deque(maxlen=4096)
        # exponentially-weighted TTFT (None until the first token is
        # emitted): the autoscaler's SLO signal — a windowed mean
        # would hide a fresh latency regression behind old samples
        self._ttft_ewma: Optional[float] = None
        self._ttft_ewma_alpha = 0.2
        # exponentially-weighted inter-token gap (online lane only):
        # the decode pool's autoscaler signal, the latency twin of
        # the TTFT EWMA above
        self._itl_ewma: Optional[float] = None
        self._itl_ewma_alpha = 0.2
        if self._block is None:
            self._decode_fn = self._track_program(_jit_decode(
                self.model, self.temperature, self.KMAX, self.S,
                self.capture_logprobs, self._mesh))
            self._seed_fn = self._track_program(_jit_seed())
        else:
            self._decode_fn = self._track_program(_jit_decode_blocks(
                self.model, self.temperature, self.KMAX, self.S,
                self.eos_id, self._mesh))
            self._seed_fn = self._track_program(_jit_seed_blocks())
        _clk.mark("programs")
        # a replica's start is this event and the ``compile`` events
        # up to its first round that builds nothing
        self.events.append("engine_init", data=_clk.parts())

    def _track_program(self, fn):
        """Register a jitted step program under its trace name. The
        builders are shared per process (lru_cache), so the baseline
        is what the program already holds, not zero."""
        name = "jit_" + fn.__name__
        self._programs[name] = fn
        self._program_sizes[name] = fn._cache_size()
        self._builds.watch(name)
        return fn

    def _count_programs_locked(self, wall_s: float) -> None:
        """After a round: which of the engine's programs gained an
        executable (built, or loaded from the persistent cache) — the
        in-program answer to "which round recompiled", and from the
        build log's records of that program by this thread since the
        engine last looked, what the build was: seconds tracing,
        lowering, in the backend (a compile, or a load from the cache
        of which ``cache_read_s`` read the file) and whether the cache
        had it. A build the cache did not have is a cold one."""
        new = None
        for name, fn in self._programs.items():
            n = fn._cache_size()
            grew = n - self._program_sizes[name]
            if grew > 0:
                if new is None:
                    me = threading.get_ident()
                    new = [r for r in self._builds.since(
                        self._build_cursor) if r["thread"] == me]
                    self._build_cursor = self._builds.total
                mine = [r for r in new if r["program"] == name]
                self._program_sizes[name] = n
                self.stats["programs_built"] += grew
                self.stats["cold_builds"] += sum(
                    r["cache_hit"] is not True for r in mine)
                self.events.append("compile", data={
                    "program": name, "round": self._round,
                    "built": grew, "wall_s": round(wall_s, 6),
                    **summarize_builds(mine)})

    # ------------------------------------------------- device trace

    def _dispatch_counts_locked(self) -> Dict[str, int]:
        """The round and how many times each step program has been
        dispatched so far: what ``trace_start`` and ``trace_stop``
        carry, so that the log says how many executions a trace holds."""
        return {"round": self._round,
                "prefills": self.stats["prefills"],
                "chunks": self.stats["chunks"],
                "verifies": self.stats["spec_rounds"]}

    def start_trace(self, log_dir: str) -> float:
        """Start a device trace (``jax.profiler``) in this process, on
        a round's edge with nothing in flight, and mark it in the event
        log. It takes the engine's lock between two rounds, reads back
        every dispatch still in flight and waits for the device, starts
        the profiler and appends ``trace_start``; only then the next
        round runs. So the trace's n-th execution of a step program IS
        the n-th dispatch of that program in a round after
        ``trace_start``'s ``round``: device executions join to rounds
        by order, checked by the counts ``trace_start`` and
        ``trace_stop`` carry (``round``, ``prefills``, ``chunks``,
        ``verifies``: cumulative dispatches of ``jit_prefill``,
        ``jit_decode``, ``jit_verify``) and by the trace's clock, which
        the device planes share with the ``engine.*`` annotations on
        the host plane (benchmarks/trace_dispatch.py). The chip idles
        while the profiler starts: that gap lies before the trace's
        first device operation; ``trace_start`` says what it cost
        (``wait_s`` for the dispatches in flight, ``start_s`` for the
        profiler). One trace at a time (a second start raises
        ``RuntimeError``). Returns time.monotonic() at the start."""
        from ray_tpu._private import profiling
        with self._lock:
            t_wait = time.monotonic()
            self._drain_fetches_locked()
            # the seed scatter and the pool trail the last readback
            jax.block_until_ready(
                (self.pages, self._dev_cur, self._dev_pos,
                 self._dev_blocks))
            t0 = profiling.start_device_trace(log_dir)
            self.events.append("trace_start", t=t0, data={
                **self._dispatch_counts_locked(), "log_dir": log_dir,
                "wait_s": round(t0 - t_wait, 6),
                "start_s": round(time.monotonic() - t0, 6)})
        return t0

    def stop_trace(self):
        """Stop the trace ``start_trace`` began and write it out;
        returns the traced span (t0, t1) on time.monotonic().
        Nothing waits on the device's side: an execution the stop cuts
        off is absent from the trace's tail. ``trace_stop`` carries the
        counts of ``trace_start``, read between two rounds just before
        the profiler stops. ``RuntimeError`` when none is running."""
        from ray_tpu._private import profiling
        with self._lock:
            counts = self._dispatch_counts_locked()
        t0, t1 = profiling.stop_device_trace()
        self.events.append("trace_stop", t=t1, data={
            **counts, "span_s": round(t1 - t0, 6)})
        return t0, t1

    def _h2d(self, x):
        """Host->device for dispatch operands (page tables, token
        chunks, positions, rng keys). Unsharded: plain jnp.asarray
        (byte-identical to the pre-TP engine). Sharded: commit
        REPLICATED onto the replica's mesh — an uncommitted
        single-device array would make every jitted call re-broadcast
        it from device 0 and spam donation warnings."""
        if self._sharding is None:
            return jnp.asarray(x)
        return self._sharding.replicate(jnp.asarray(x))

    # ---------------------------------------------------------- public

    def submit(self, prompt_ids: List[int],
               max_new_tokens: int = 64,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               pull: Optional[Dict[str, Any]] = None,
               priority: str = LANE_ONLINE) -> RequestHandle:
        """Queue one request. ``deadline_s`` (relative, seconds) sets
        a hard completion deadline: the request fails with
        ``DeadlineExceeded`` at whatever phase it is in — queued,
        mid-prefill, decoding, mid-speculation — the first scheduling
        round after the deadline passes, and its resources free
        immediately. With ``max_queued`` configured, a full admission
        queue sheds the request with ``EngineOverloaded`` instead of
        accepting unbounded latency.

        ``priority`` selects the lane: ``"online"`` (default, the
        latency-critical path) or ``"batch"`` (preemptible offline
        work, serve/batch_tier.py). A batch request admits only when
        no online request is waiting, yields its slot the moment
        online traffic needs it (recompute/prefix-cache resume on
        re-admission, token-identical), and is bounded by
        ``max_queued_batch`` instead of ``max_queued`` — so a deep
        batch backlog can neither shed nor delay online admission.

        ``pull`` is a cross-replica KV pull hint from pool routing
        (serve/kv_migration.py): a dict carrying at least ``hashes``
        (the prompt's leading rolling path hashes a peer replica
        advertised as resident) plus whatever opaque fields the
        injected ``kv_fetcher`` needs to reach the donor. Admission
        then enters the PULLING phase instead of recomputing the
        prefix — see ``_admit_locked``. Ignored without a fetcher or
        prefix cache."""
        prompt_ids = [int(t) for t in prompt_ids]
        if not prompt_ids:
            raise RequestError("empty prompt")
        if max_new_tokens < 1:
            raise RequestError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise RequestError("deadline_s must be > 0")
        if priority not in (LANE_ONLINE, LANE_BATCH):
            raise RequestError(
                f"unknown priority {priority!r}; expected "
                f"'{LANE_ONLINE}' or '{LANE_BATCH}'")
        total = len(prompt_ids) + max_new_tokens
        if self._block is not None:
            # the last block is written whole
            L = self._block.block_length
            total = -(-total // L) * L
        need = -(-total // self.Pg)
        if need > self.alloc.n_pages - 1:
            raise RequestError(
                f"request needs {need} pages but pool has only "
                f"{self.alloc.n_pages - 1} usable pages")
        if total > self.cfg.max_seq_len:
            raise RequestError(
                f"prompt+completion {total} exceeds model "
                f"max_seq_len {self.cfg.max_seq_len}")
        req = _Request(next(self._rid), prompt_ids, max_new_tokens,
                       t_submit=time.monotonic(), trace_id=trace_id,
                       pull=pull, batch=(priority == LANE_BATCH))
        if self.capture_logprobs:
            req.logprobs = []
        if self.record_reveals and self._block is not None:
            req.reveal_steps = []
        if deadline_s is not None:
            req.deadline = req.t_submit + deadline_s
        self.events.append("submit", rid=req.rid, t=req.t_submit,
                           data={"trace_id": trace_id,
                                 "prompt_len": len(prompt_ids),
                                 "max_new_tokens": max_new_tokens,
                                 "lane": priority})
        # Bounded admission-lock acquire: the scheduler holds this
        # lock across whole rounds, and a WEDGED scheduler (hung
        # dispatch — see serve/watchdog.py) holds it forever. With a
        # timeout configured, a stalled acquire sheds typed
        # EngineOverloaded instead of parking the caller on a lock
        # only teardown would release — the pool treats the shed as
        # "exclude this replica and route on".
        if self.admit_timeout_s is not None:
            acquired = self._work.acquire(
                timeout=self.admit_timeout_s)
        else:
            acquired = self._work.acquire()
        if not acquired:
            self.stats["admit_timeouts"] += 1
            self.events.append("shed", rid=req.rid,
                               data={"why": "admit_timeout"})
            raise EngineOverloaded(
                f"admission lock unavailable for "
                f"{self.admit_timeout_s}s (scheduler stalled); "
                f"request shed",
                retry_after_s=self.shed_retry_after_s)
        try:
            if self._stopped:
                raise EngineShutdown("engine stopped")
            if self._draining:
                raise EngineDraining(
                    "engine draining: finishing in-flight work, "
                    "admitting nothing new")
            # Per-lane bounded admission: the online bound counts
            # only online requests (a deep preemptible batch backlog
            # must never shed latency-critical traffic), and the
            # batch lane carries its own, typically much deeper (or
            # unbounded) budget — the throughput profile's
            # no-TTFT-SLO deep queue.
            bound = (self.max_queued_batch if req.batch
                     else self.max_queued)
            if bound is not None:
                lane_depth = sum(1 for r in self._wait
                                 if r.batch == req.batch)
                if lane_depth >= bound:
                    self.stats["shed"] += 1
                    _metrics()["shed"].inc()
                    self.events.append(
                        "shed", rid=req.rid,
                        data={"why": "queue_full",
                              "lane": priority})
                    raise EngineOverloaded(
                        f"admission queue full ({lane_depth} "
                        f"{priority} waiting >= "
                        f"max_queued{'_batch' if req.batch else ''}="
                        f"{bound}); request shed",
                        retry_after_s=self.shed_retry_after_s)
            self._wait.append(req)
            self.stats["submitted"] += 1
            self._work.notify()
        finally:
            self._work.release()
        return RequestHandle(req, self)

    def submit_rollout_batch(self, prompts: List[List[int]],
                             max_new_tokens: int = 64,
                             deadline_s: Optional[float] = None,
                             trace_id: Optional[str] = None
                             ) -> List[RequestHandle]:
        """Rollout-batch submit surface (ray_tpu/rl): queue one
        BATCH-lane request per prompt, in order, and return the
        handles. Batch-lane semantics are exactly the RL generator's
        needs — admits only behind online traffic, first preemption
        victim, excluded from the TTFT SLO signals — so a co-located
        online workload keeps its latency while rollouts soak the
        leftover capacity. ``trace_id`` (if given) stamps each
        request as ``{trace_id}:{i}``; per-token logprobs ride the
        handles when the engine was built with
        ``capture_logprobs=True``."""
        return [self.submit(list(p), max_new_tokens=max_new_tokens,
                            deadline_s=deadline_s,
                            trace_id=(f"{trace_id}:{i}"
                                      if trace_id else None),
                            priority=LANE_BATCH)
                for i, p in enumerate(prompts)]

    def start(self) -> "LLMEngine":
        """Run the scheduler loop in a daemon thread."""
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="llm-engine", daemon=True)
            self._thread.start()
        return self

    def drain(self) -> None:
        """Enter drain mode: admit nothing new, finish everything
        already queued or in flight. Direct ``submit`` calls fail
        typed ``EngineDraining`` (503 at the proxy); pool routing
        skips draining replicas entirely. Idempotent. Pair with
        ``wait_idle`` then ``shutdown`` for a graceful restart."""
        with self._work:
            self._draining = True
            self._work.notify_all()

    @property
    def draining(self) -> bool:
        return self._draining

    def reset_latency_stats(self) -> None:
        """Forget TTFT samples and the EWMA accumulated so far.
        For warmup scrubbing: a deployment compiles a replica with a
        throwaway request before it joins the fleet, and that
        compile-priced TTFT is not client experience — left in the
        EWMA it reads as a permanent SLO breach to the autoscaler."""
        with self._lock:
            self.ttfts_s.clear()
            self._ttft_ewma = None
            self._itl_ewma = None

    def is_idle(self) -> bool:
        """True when no request is queued, slotted, or trailing in a
        readback — the state a draining replica must reach before it
        can restart without failing anyone."""
        with self._lock:
            return (not self._wait and not any(self.slots)
                    and not self._fetchq
                    and not self._pending_prefill)

    def wait_idle(self, timeout_s: float = 30.0) -> bool:
        """Block until ``is_idle`` (or timeout). Returns the final
        idleness — False means in-flight work outlived the budget and
        the caller decides whether to axe it (``shutdown``)."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while not self.is_idle():
            if time.monotonic() >= deadline:
                return self.is_idle()
            time.sleep(0.005)
        return True

    # ------------------------------------------- live weight rollout

    def swap_weights(self, params, *, generation: Optional[int] = None,
                     weights_id: Optional[str] = None,
                     mode: str = "preempt", wait: bool = True,
                     timeout_s: float = 120.0) -> int:
        """In-place hot weight swap under traffic.

        The new payload is staged onto the device OFF the engine lock
        (the double buffer: the old generation keeps serving while the
        transfer runs), then the flip happens between scheduler rounds
        — ``step()`` holds the engine lock for its entire round, so
        taking the lock here IS the inter-round boundary.

        ``mode="preempt"`` (default) flips immediately: trailing
        readbacks are drained so every victim's generated-so-far is
        complete, every active slot is preempted through the ordinary
        token-identical recompute path (the same arm replica death
        uses — prompt + generated re-prefill at the queue front), the
        prefix cache is cleared (no KV computed under the old weights
        may ever be matched against new-weight decode; per-slot spec
        proposers die with their slots), and the fence advances.

        ``mode="drain"`` pauses admission and applies the same flip
        once every slot, trailing readback, and pending prefill has
        settled — in-flight requests finish wholly on the old weights.

        The fence is strictly monotonic: a ``generation`` at or below
        the current one is refused with ``ValueError``. Roll BACK by
        installing the old payload under a NEW generation (a distinct
        ``weights_id`` names the payload). Returns the generation
        serving after the swap (with ``wait=False`` in drain mode:
        the generation that WILL serve once the drain settles)."""
        if mode not in ("preempt", "drain"):
            raise ValueError(f"unknown swap mode {mode!r}; expected "
                             f"'preempt' or 'drain'")
        if self._sharding is not None:
            staged = self._sharding.shard_params(params)
        else:
            staged = jax.tree_util.tree_map(jnp.asarray, params)
        jax.block_until_ready(jax.tree_util.tree_leaves(staged))
        with self._work:
            if self._stopped:
                raise EngineShutdown(
                    "cannot swap weights: engine stopped")
            gen = (self.weight_generation + 1 if generation is None
                   else int(generation))
            if gen <= self.weight_generation:
                raise ValueError(
                    f"weight-generation fence is monotonic: requested "
                    f"generation {gen} <= current "
                    f"{self.weight_generation} (install the old "
                    f"payload under a NEW generation to roll back)")
            wid = weights_id if weights_id is not None else f"g{gen}"
            if mode == "preempt":
                self._apply_swap_locked(staged, gen, wid, mode)
                self._work.notify_all()
                return gen
            if self._pending_swap is not None:
                raise RuntimeError(
                    "a drain-mode weight swap is already pending "
                    f"(generation "
                    f"{self._pending_swap['generation']})")
            pend = {"params": staged, "generation": gen,
                    "weights_id": wid, "applied": False,
                    "event": threading.Event()}
            self._pending_swap = pend
            self.events.append("weight_swap_pending",
                               data={"generation": gen,
                                     "weights_id": wid})
            self._work.notify_all()
        if not wait:
            return gen
        deadline = time.monotonic() + max(0.0, timeout_s)
        while not pend["event"].wait(timeout=0.05):
            if self._stopped:
                raise EngineShutdown(
                    "engine stopped with a weight swap pending")
            if time.monotonic() >= deadline:
                with self._work:
                    if self._pending_swap is pend:
                        self._pending_swap = None
                raise TimeoutError(
                    f"drain-mode weight swap to generation {gen} did "
                    f"not apply within {timeout_s}s")
        if not pend["applied"]:
            raise EngineShutdown(
                "engine stopped with a weight swap pending")
        return gen

    def _maybe_apply_pending_swap_locked(self) -> None:
        """Apply a pending drain-mode swap iff the engine has fully
        settled (no slots, no trailing readbacks, no in-flight
        prefills). Called between rounds by ``step()``."""
        pend = self._pending_swap
        if pend is None:
            return
        if (any(s is not None for s in self.slots) or self._fetchq
                or self._pending_prefill):
            return
        self._pending_swap = None
        self._apply_swap_locked(pend["params"], pend["generation"],
                                pend["weights_id"], "drain")
        pend["applied"] = True
        pend["event"].set()

    def _apply_swap_locked(self, staged, gen: int, wid: str,
                           mode: str) -> None:
        """The inter-round flip. Caller holds the engine lock and has
        validated the fence."""
        # settle trailing readbacks first so every preemption victim's
        # generated-so-far is complete before its recompute prompt
        # freezes (token-identity across the swap)
        self._drain_fetches_locked()
        preempted = 0
        for i in range(len(self.slots)):
            victim = self.slots[i]
            if victim is None:
                continue
            self._preempt_locked(i)
            if victim.preempted:
                preempted += 1
        # the fence's cache half: every slot was preempted (all shared
        # references released), so clear() evicts the whole radix tree
        # — no old-generation KV page survives to be matched against
        # new-generation decode
        evicted = 0
        if self.prefix_cache is not None:
            evicted = self.prefix_cache.clear()
        self.params = staged
        self.weight_generation = gen
        self.weights_id = wid
        self.stats["weight_swaps"] += 1
        _metrics()["weight_swaps"].inc()
        _weight_generation_gauge().set(
            float(gen),
            tags={"replica": str(getattr(self, "replica_tag", "0"))})
        self.events.append("weight_swap", data={
            "generation": gen, "weights_id": wid, "mode": mode,
            "preempted": preempted,
            "prefix_pages_evicted": evicted})
        self._hb = time.monotonic()

    def load_report(self) -> Dict[str, Any]:
        """Compact load snapshot for pool routing: free capacity,
        queue pressure, outstanding token work, and the prefix-cache
        digest (``PrefixCache.digest``) that longest-prefix affinity
        matches against.

        Best-effort consistency by design: tries the engine lock
        briefly, and otherwise reads lock-free — the scheduler
        mutates these fields under the GIL, so individual reads are
        safe and routing only needs freshness, not atomicity. A
        torn read costs one suboptimal route, never correctness."""
        def compute() -> Dict[str, Any]:
            outstanding = 0
            free_slots = 0
            for slot in list(self.slots):
                if slot is None:
                    free_slots += 1
                    continue
                req = slot.req
                outstanding += max(0, len(slot.prompt)
                                   - slot.prefilled)
                outstanding += max(0, req.max_new_tokens
                                   - len(req.generated))
            waiting = list(self._wait)
            for req in waiting:
                outstanding += len(req.prompt) + req.max_new_tokens
            q_batch = sum(1 for r in waiting if r.batch)
            return {
                "free_slots": free_slots,
                "total_slots": len(self.slots),
                "free_pages": self.alloc.n_free,
                # dtype-aware bytes view: the halving int8 buys shows
                # up wherever load_report lands (autoscaler signals,
                # pool_stats, flight bundles)
                "kv_dtype": self.kv_dtype,
                "kv_page_bytes": self.page_bytes,
                # what one token of context costs over the layers
                # that have pages, K and V a head or one latent entry
                # (an int8 page's scales shared among its tokens)
                "kv_bytes_per_token": self.page_bytes / self.Pg,
                "kv_bytes_in_use": self.alloc.bytes_in_use(),
                "kv_bytes_total": self.alloc.bytes_total(),
                # the other kind of request state: what the slots
                # hold in layers that keep a recurrent state or a
                # sliding window's ring (0 for a model with pages
                # only); a slot's share of it that is rings
                "state_bytes_in_use": self.state_bytes_per_slot
                * (len(self.slots) - free_slots),
                "state_bytes_total": self.state_bytes_per_slot
                * len(self.slots),
                "sliding_bytes_per_slot": self.sliding_bytes_per_slot,
                # Per-lane queue depth. ``queue_depth`` is the ONLINE
                # lane only — the number routing saturation
                # (Candidate.saturated vs max_queued) and the
                # autoscaler compare against their online-lane
                # bounds. Preemptible batch backlog is deliberately
                # its own number: scaling the fleet up for work that
                # yields instantly would defeat the tier.
                "queue_depth": len(waiting) - q_batch,
                "queue_depth_online": len(waiting) - q_batch,
                "queue_depth_batch": q_batch,
                "outstanding_tokens": outstanding,
                "max_queued": self.max_queued,
                "max_queued_batch": self.max_queued_batch,
                "shed_retry_after_s": self.shed_retry_after_s,
                "shed_total": self.stats.get("shed", 0),
                # executables the step programs gained and those the
                # cache lacked; ring positions the sliding kernel read
                "programs_built": self.stats.get("programs_built", 0),
                "cold_builds": self.stats.get("cold_builds", 0),
                "sliding_kernel_keys": self.stats["sliding_kernel_keys"],
                "ttft_ewma_s": self._ttft_ewma,
                "itl_ewma_s": self._itl_ewma,
                "role": self.role,
                "weight_generation": self.weight_generation,
                "weights_id": self.weights_id,
                "draining": self._draining,
                "stopped": self._stopped,
                "heartbeat_age_s": time.monotonic() - self._hb,
                # readback accounting: dispatches whose tokens are
                # still in flight. The overlapped loop holds this at
                # <= 2 (double-buffered) in steady state; a growing
                # depth means the trailing drain is starved.
                "fetchq_depth": len(self._fetchq),
                "pending_prefills": len(self._pending_prefill),
                "overlap": self.overlap,
                "has_work": bool(waiting or any(self.slots)
                                 or self._fetchq
                                 or self._pending_prefill),
                "tp": (self._sharding.tp
                       if self._sharding is not None else 1),
                "prefix_digest": (self.prefix_cache.digest(
                    self.prefix_digest_max)
                    if self.prefix_cache is not None
                    else frozenset()),
                **self.accounts.load_report(),
            }
        if self._lock.acquire(timeout=0.02):
            try:
                return compute()
            finally:
                self._lock.release()
        for _ in range(3):
            try:
                return compute()
            except RuntimeError:     # dict/deque mutated mid-iteration
                continue
        return {"free_slots": 0, "total_slots": len(self.slots),
                "free_pages": self.alloc.n_free,
                "kv_dtype": self.kv_dtype,
                "kv_page_bytes": self.page_bytes,
                "kv_bytes_per_token": self.page_bytes / self.Pg,
                "kv_bytes_in_use": self.alloc.bytes_in_use(),
                "kv_bytes_total": self.alloc.bytes_total(),
                "state_bytes_in_use": 0,
                "state_bytes_total": self.state_bytes_per_slot
                * len(self.slots),
                "sliding_bytes_per_slot": self.sliding_bytes_per_slot,
                "queue_depth": len(self._wait),
                "queue_depth_online": len(self._wait),
                "queue_depth_batch": 0,
                "outstanding_tokens": 0,
                "max_queued": self.max_queued,
                "max_queued_batch": self.max_queued_batch,
                "shed_retry_after_s": self.shed_retry_after_s,
                "shed_total": self.stats.get("shed", 0),
                # executables the step programs gained and those the
                # cache lacked; ring positions the sliding kernel read
                "programs_built": self.stats.get("programs_built", 0),
                "cold_builds": self.stats.get("cold_builds", 0),
                "sliding_kernel_keys": self.stats["sliding_kernel_keys"],
                "ttft_ewma_s": self._ttft_ewma,
                "itl_ewma_s": self._itl_ewma,
                "role": self.role,
                "weight_generation": self.weight_generation,
                "weights_id": self.weights_id,
                "draining": self._draining,
                "stopped": self._stopped,
                "heartbeat_age_s": time.monotonic() - self._hb,
                "fetchq_depth": len(self._fetchq),
                "pending_prefills": len(self._pending_prefill),
                "overlap": self.overlap,
                "has_work": bool(self._wait or any(self.slots)
                                 or self._fetchq
                                 or self._pending_prefill),
                "tp": (self._sharding.tp
                       if self._sharding is not None else 1),
                "prefix_digest": frozenset()}

    def force_kill(self, err: Optional[BaseException] = None) -> None:
        """Out-of-band kill for a WEDGED engine (watchdog escalation,
        serve/watchdog.py). A wedged scheduler thread is parked INSIDE
        ``step()`` HOLDING ``self._lock`` — every fault site fires
        under it — so ``shutdown()``'s lock-then-join would deadlock.
        This path takes NO lock: it sets the zombie fence + stop flag
        (GIL-atomic assignments) and fails every consumer so blocked
        ``stream()`` callers unblock immediately and the pool can
        resubmit. Resource cleanup (slot pages) happens later, when
        the wedge releases and the zombie thread unwinds — call
        ``shutdown()`` again after that for the final teardown.

        Zombie fence: after this, a step thread that later wakes
        cannot commit tokens (requests are closed; ``_emit_to``
        drops), cannot dispatch (the post-fire ``_stopped`` checks
        abandon the round), and cannot publish pages into the prefix
        cache (retire-path inserts divert to plain frees)."""
        err = err or EngineShutdown(
            "engine force-killed: wedged (no scheduler progress)")
        self.events.append("force_kill", data={"error": repr(err)})
        self._force_killed = True
        self._stopped = True

        def fail(req):
            if req.closed:
                return
            req.closed = True
            req.error = err
            req.out_q.put(_DONE)

        for slot in list(self.slots):
            if slot is not None:
                fail(slot.req)
        for item in list(self._fetchq):
            for _i, slot, _t in item[1]:
                fail(slot.req)
        for item in list(self._pending_prefill):
            for _ix, slot, _row in item[1]:
                fail(slot.req)
        for req in list(self._wait):
            fail(req)
        pend, self._pending_swap = self._pending_swap, None
        if pend is not None:
            pend["event"].set()   # waiter sees applied=False + raises
        self.stats["force_killed"] += 1

    def shutdown(self):
        """Stop the engine and FAIL everything still queued or in
        flight with a typed ``EngineShutdown`` — no ``stream()``/
        ``result()`` consumer may be left blocked. Tokens already
        computed (trailing readbacks of retired slots) are delivered
        first, so a request that effectively finished still resolves
        cleanly. Idempotent.

        After a ``force_kill`` the scheduler thread may still be
        wedged inside ``step()`` holding the engine lock, so this
        path must not block on it: the join is short and a
        still-alive thread defers the final resource cleanup to a
        later ``shutdown()`` call (after the wedge releases —
        ``FaultInjector.release_all()`` in tests)."""
        err = EngineShutdown("engine stopped")
        if self._force_killed:
            # consumers already failed lock-free; taking the lock
            # here would deadlock against the wedged step thread
            if self._thread is not None:
                self._thread.join(timeout=1.0)
                if self._thread.is_alive():
                    return      # still wedged: cleanup deferred
        else:
            with self._work:
                self._stopped = True
                self._work.notify_all()
            if self._thread is not None:
                self._thread.join(timeout=30)
        with self._work:
            # deliver what the device already produced before the axe
            try:
                self._drain_fetches_locked()
            except Exception:
                pass     # device gone: typed failure below still lands
            for i, slot in enumerate(self.slots):
                if slot is not None:
                    self._teardown_slot_locked(i, err)
            for _buf, riders, _steps in self._fetchq:
                for _i, slot, _t in riders:
                    self._fail_req_locked(slot.req, err)
            for _f, placements in self._pending_prefill:
                for _ix, slot, _row in placements:
                    self._fail_req_locked(slot.req, err)
            self._fetchq.clear()
            self._pending_prefill.clear()
            while self._wait:
                self._fail_req_locked(self._wait.popleft(), err)
            pend, self._pending_swap = self._pending_swap, None
            if pend is not None:
                pend["event"].set()   # waiter raises EngineShutdown

    def _cancel(self, req: _Request,
                error: Optional[BaseException] = None) -> bool:
        """Abort ``req`` at any phase (RequestHandle.cancel). Queued:
        removed and failed on the spot. Slotted (mid-prefill,
        decoding, mid-speculation): torn down synchronously — the
        lock serializes against the scheduler, and freeing pages
        under an in-flight dispatch is safe because device execution
        is stream-ordered (the same argument _retire_planned_locked
        rests on); trailing readbacks skip the closed request.
        Already-retired requests with tokens still in flight just
        close. Returns False iff the request had already finished."""
        err = error or RequestCancelled(
            f"request {req.rid} cancelled by client")
        # Flag first, lock second. The scheduler re-takes its lock
        # back to back between rounds and Python locks are not fair:
        # a canceller can lose every hand-off until the request has
        # decoded to completion. With the flag up, the scheduler's
        # own next round does the teardown (_reap_deadlines_locked).
        if req.cancel_error is None:
            req.cancel_error = err
        with self._work:
            if req.closed:
                return req.error is req.cancel_error
            try:
                self._wait.remove(req)
                self._fail_req_locked(req, err, "cancelled")
                return True
            except ValueError:
                pass
            for i, slot in enumerate(self.slots):
                if slot is not None and slot.req is req:
                    self._teardown_slot_locked(i, err, "cancelled")
                    self._work.notify()
                    return True
            self._fail_req_locked(req, err, "cancelled")
            return True

    def _fail_req_locked(self, req: _Request, err: BaseException,
                         count: Optional[str] = None) -> None:
        """Resolve a request's consumers with a typed error, exactly
        once. ``count`` names the stats/metrics counter to bump."""
        if req.closed:
            return
        req.closed = True
        req.error = err
        req.out_q.put(_DONE)
        self.events.append(count or "failed", rid=req.rid,
                           data={"error": repr(err)})
        if count:
            self.stats[count] += 1
            m = _metrics().get(count)
            if m is not None:
                m.inc()

    def _teardown_slot_locked(self, ix: int, err: BaseException,
                              count: Optional[str] = None) -> None:
        """Fail a slotted request and free every resource it holds:
        the slot, its private pages (back to the allocator), and its
        shared prefix-page references (the tree keeps the KV).
        ``preempted`` is set so in-flight readback rows for this slot
        are discarded rather than emitted."""
        slot = self.slots[ix]
        self.slots[ix] = None
        slot.preempted = True
        self._free_slot_pages_locked(slot, retire=False)
        self._fail_req_locked(slot.req, err, count)

    def _reap_deadlines_locked(self) -> None:
        """Tear down requests whose cancel flag is up, then expire
        those whose deadline passed — queued or slotted alike — with
        ``DeadlineExceeded``. Runs at the top of every scheduling
        round, so enforcement granularity is one round."""
        for req in [r for r in self._wait
                    if r.cancel_error is not None]:
            self._wait.remove(req)
            self._fail_req_locked(req, req.cancel_error, "cancelled")
        for i, slot in enumerate(self.slots):
            if (slot is not None and not slot.req.closed
                    and slot.req.cancel_error is not None):
                self._teardown_slot_locked(i, slot.req.cancel_error,
                                           "cancelled")
        now = time.monotonic()
        for req in [r for r in self._wait if r.deadline is not None
                    and now >= r.deadline]:
            self._wait.remove(req)
            self._fail_req_locked(req, DeadlineExceeded(
                f"request {req.rid} missed its deadline while "
                f"queued"), "deadline_exceeded")
        for i, slot in enumerate(self.slots):
            if slot is None or slot.req.closed:
                continue
            if (slot.req.deadline is not None
                    and now >= slot.req.deadline):
                self._teardown_slot_locked(i, DeadlineExceeded(
                    f"request {slot.req.rid} missed its deadline "
                    f"after {len(slot.req.generated)} tokens"),
                    "deadline_exceeded")

    def _fire(self, site: str, sid: Optional[int] = None,
              rid: Optional[int] = None) -> None:
        """Fault-injection site (no-op without an injector)."""
        if self._injector is not None:
            self._injector.fire(site, self._round, sid, rid)

    def _alloc(self, n: int) -> Optional[List[int]]:
        """BlockAllocator.alloc behind the fault seam: an injected
        exhaustion makes the pool look dry for this one call,
        steering the caller into its real evict/preempt/wait
        recovery path."""
        if (self._injector is not None
                and self._injector.exhausted(self._round)):
            return None
        return self.alloc.alloc(n)

    def lifecycle_stats(self) -> Dict[str, Any]:
        """Request-lifecycle knobs + counters (bench artifacts and
        the replica stats hook read this)."""
        with self._lock:
            s = self.stats
            return {
                "max_queued": self.max_queued,
                "max_retries": self.max_retries,
                "retry_backoff_s": self.retry_backoff_s,
                "shed": s["shed"],
                "cancelled": s["cancelled"],
                "deadline_exceeded": s["deadline_exceeded"],
                "contained_faults": s["contained_faults"],
                "retries": s["retries"],
                "retry_exhausted": s["retry_exhausted"],
                "fault_failed": s["fault_failed"],
            }

    def step(self) -> bool:
        """One scheduler iteration, DEVICE-PACED:

            admit -> plan round -> dispatch prefill chunk
                  -> grow/preempt -> dispatch decode chunk k+1
                  -> fetch chunk k's tokens (trailing)

        The round packs a prefill chunk AND a decode chunk: both are
        dispatched asynchronously back to back, so the device
        pipeline interleaves ``P D P D ...`` and in-flight decode is
        delayed by at most one bounded prefill chunk per round —
        never by a whole prompt. Decode dispatch k+1 has NO data
        dependency on k's readback: the next-token input and write
        positions chain on device (dev_cur/dev_pos), seeding rides a
        jitted scatter, and — with no eos configured — completions
        are dispatch-time arithmetic. The readback of chunk k then
        overlaps chunk k+1's compute, so neither the device round
        trip nor a slow host thread gates the token rate.

        With an eos the loop is DOUBLE-BUFFERED (``overlap=True``,
        the default): the pre-plan drain is a non-blocking sweep, so
        round N+1 is planned from round N's (stale) frontier and its
        dispatches are committed while round N still executes; the
        trailing drain at the bottom then blocks on the OLDER of the
        two in-flight dispatches only (keep=1), pinning the pipeline
        depth at two and revealing each round's tokens at most one
        round late. A late-revealed eos costs at most one discarded
        decode chunk per slot — the planner caps stale riders
        (serve/scheduler.py) and emission truncates exactly where
        lockstep would. ``overlap=False`` restores the lockstep
        profile: sampled tokens decide completion, so the iteration
        drains readbacks fully before planning (the classic chunked
        loop). Returns False when idle.

        Failure containment: an ``EngineFault`` out of a dispatch
        section (fault-injection sites, or the now-attributable
        pool-exhausted-by-one-slot path) is handled HERE — the
        culprit request fails, the other participants of that
        dispatch requeue-or-fail under the bounded retry policy —
        and the engine keeps serving. Only non-attributable errors
        still escape to ``_fail_all`` via ``_loop``."""
        # metadata_keyed: the step programs' named scopes are read
        # from device traces, so the persistent compile cache must not
        # hand back an executable that names its operations otherwise
        with self._lock, metadata_keyed():
            self._round += 1
            self._hb = time.monotonic()   # progress heartbeat: a new
                                          # round means the previous
                                          # one completed
            _pm = obs.phase_metrics()
            _t0 = self._hb
            # The engine.* TraceAnnotations below put this round's host
            # phases on a device trace's host plane, each with the round
            # number: they label the device's idle gaps, and an
            # execution that start_trace's order joins to this round
            # must start after its engine.dispatch_* opened (the planes
            # share the trace's clock). Closed, with no trace running,
            # one costs under a microsecond.
            _rnd = self._round
            _ri = self.accounts.begin_round()
            self._fire("step")     # global-fault site: escapes to
                                   # _fail_all, like real device loss
            if self._stopped:
                # force-killed while wedged at the step site: the
                # zombie fence forbids any further work this round
                return False
            self._reap_deadlines_locked()
            _tg = time.monotonic()
            with TraceAnnotation("engine.drain_ready", round=_rnd):
                if self.overlap:
                    # Overlapped hot loop: plan round N+1 from the STALE
                    # token frontier while round N still runs on device.
                    # This sweep only reads buffers the device already
                    # finished — it NEVER blocks, in eos mode either.
                    # Completion detection moves to the trailing drain:
                    # emission truncates at a late-revealed eos, the
                    # planner caps stale riders at one decode chunk
                    # (serve/scheduler.py SlotView.stale), and the
                    # overshot KV frontier is reclaimed by the same
                    # clamp-and-reseed machinery spec rollback uses. Spec
                    # mode still syncs, but at its own dispatch
                    # (_dispatch_spec_locked) — acceptance gates the NEXT
                    # verify, not this round's prefill/decode lanes.
                    self._drain_fetches_locked(ready_only=True)
                elif not self._deferred or self.spec_len:
                    # Lockstep eos mode: emissions gate planning. Spec
                    # mode: the proposer's context and the verify's input
                    # token are HOST state (req.generated), so every
                    # round syncs to the device before planning —
                    # speculation trades the deferred pipeline's async
                    # pacing for multi-token dispatches.
                    self._drain_fetches_locked()
                else:
                    # Opportunistic: read back anything already finished
                    # BEFORE admitting — free on a fast local device, and
                    # it gets completions to clients (whose resubmissions
                    # can then land during the upcoming dispatch) a full
                    # dispatch earlier. Never blocks.
                    self._drain_fetches_locked(ready_only=True)
            _ta = time.monotonic()
            _ca = time.thread_time()
            _gap = _ta - _tg
            if self._pending_swap is not None:
                # drain-mode weight swap: admission is paused; flip
                # here — between rounds — once everything settled
                self._maybe_apply_pending_swap_locked()
            if self._hold_idle_admission_locked():
                return True
            with TraceAnnotation("engine.admit", round=_rnd):
                self._admit_locked()
            if not any(self.slots):
                if self._fetchq or self._pending_prefill:
                    self._drain_fetches_locked(limit=1)
                    return True
                # non-empty queue with nothing admitted = retry
                # backoff or a transiently dry pool: still working
                return bool(self._wait)
            if all(s is None or s.pulling for s in self.slots):
                # only PULLING slots live: nothing is dispatchable
                # until a transfer lands or aborts. Park on the
                # condition (the pull thread notifies on finish)
                # instead of spinning rounds; readbacks of already-
                # retired slots still drain.
                if self._fetchq or self._pending_prefill:
                    self._drain_fetches_locked(limit=1)
                else:
                    self._work.wait(timeout=0.01)
                return True
            _tp = time.monotonic()
            with TraceAnnotation("engine.plan", round=_rnd):
                plan = self._plan_steps_locked()
            _tpe = time.monotonic()
            _gap += _tpe - _tp
            _pm["plan"].observe(_tpe - _tp)
            try:
                if plan.prefill:
                    with TraceAnnotation("engine.dispatch_prefill",
                                         round=_rnd):
                        self._dispatch_prefill_locked(plan.prefill)
            except EngineFault as e:
                e.sids = sorted({g.sid for g in plan.prefill}
                                | set(e.sids))
                self._contain_fault_locked(e)
                return True
            try:
                if plan.spec:
                    with TraceAnnotation("engine.dispatch_spec",
                                         round=_rnd):
                        self._dispatch_spec_locked(plan.spec)
                elif plan.decode_steps:
                    riders = [i for i, s in enumerate(self.slots)
                              if s is not None and s.cur is not None]
                    with TraceAnnotation("engine.dispatch_decode",
                                         round=_rnd):
                        self._grow_or_preempt_locked(plan.decode_steps)
                        self._dispatch_chunk_locked(plan.decode_steps)
                    if self._deferred:
                        self._retire_planned_locked()
            except EngineFault as e:
                part = ({g.sid for g in plan.spec} if plan.spec
                        else set(riders))
                e.sids = sorted(part | set(e.sids))
                self._contain_fault_locked(e)
                return True
            _tde = time.monotonic()
            _cpu = time.thread_time() - _ca
            _pm["dispatch"].observe(_tde - _tpe)
            # trailing readback: block only on a dispatch OLDER than
            # the one just queued (keep=1), so the fetch round trip
            # overlaps the newest dispatch's compute — never its own
            with TraceAnnotation("engine.readback", round=_rnd):
                self._drain_fetches_locked(limit=1, keep=1)
            _now = time.monotonic()
            _cpu_rb = time.thread_time() - _ca - _cpu
            # Per-round pipeline accounting: host_gap is the time the
            # host spent GATING this round's dispatches (pre-plan
            # drain + plan) — the fraction of round wall during which
            # the device could not be fed. The lockstep eos loop pays
            # a full device sync here every round; the overlapped
            # loop pays only a ready-buffer sweep. trace_report
            # derives overlap efficiency from these events; the
            # serve_phase_host_gap_s histogram is the aggregate
            # cross-check.
            # The rest says what the round was: its number (the join
            # key with a device trace's engine.* annotations), where
            # the host's time went (admit/plan/dispatch wait for no
            # device, and cpu_s is this thread's CPU time over the
            # three: their wall less cpu_s is time the loop's thread
            # did not run; readback_s is the trailing drain, which
            # waits for the device, so of it readback_cpu_s is what
            # the thread spent emitting and not waiting), and what
            # was dispatched against the planner's budget.
            self.events.append("round", data={
                "host_gap_s": round(_gap, 6),
                "wall_s": round(_now - _t0, 6),
                "overlap": self.overlap,
                "round": _rnd,
                "admit_s": round(_tp - _ta, 6),
                "plan_s": round(_tpe - _tp, 6),
                "dispatch_s": round(_tde - _tpe, 6),
                "readback_s": round(_now - _tde, 6),
                "cpu_s": round(_cpu, 6),
                "readback_cpu_s": round(_cpu_rb, 6),
                **_ri, **self.accounts.take()})
            _pm["round_wall"].observe(_now - _t0)
            _pm["host_gap"].observe(_gap)
            self._count_programs_locked(_now - _t0)
            return True

    def _hold_idle_admission_locked(self) -> bool:
        """``batch_wait_timeout_s``: an engine with nothing live or in
        flight waits (the lock released) until the prefill call's
        rows can be filled from the queue or its oldest request has
        waited long enough. True while it holds."""
        if (self.batch_wait_timeout_s <= 0 or not self._wait
                or len(self._wait) >= self._max_prefill_batch
                or any(self.slots) or self._fetchq
                or self._pending_prefill):
            return False
        left = (self._wait[0].t_submit + self.batch_wait_timeout_s
                - time.monotonic())
        if left <= 0:
            return False
        self._work.wait(timeout=left)     # submit() notifies
        return True

    def _contain_fault_locked(self, e: EngineFault) -> None:
        """Per-slot failure containment: fail ONLY the culprit (the
        request the fault is attributable to) with the underlying
        error; every other slot that was participating in the
        poisoned dispatch is requeued tail-of-queue (recompute, like
        preemption) under the bounded retry policy — ``max_retries``
        attempts with exponential backoff — instead of dying with
        it. A fault with no culprit (whole-dispatch transient)
        requeues every participant. Replaces the old blanket
        ``_fail_all`` for everything short of genuine global errors
        (device loss), which still take that path."""
        self.stats["contained_faults"] += 1
        _metrics()["contained_faults"].inc()
        self.events.append("fault", rid=e.culprit_rid,
                           sid=e.culprit_sid,
                           data={"sids": list(e.sids),
                                 "error": repr(e.original)})
        if self.flight_dir is not None:
            # postmortem bundle while the fault context is still live
            # (probing is lock-free, so holding self._lock is fine)
            obs.dump_flight_bundle(self.flight_dir, "engine-fault",
                                   engine=self)
        # settle trailing readbacks first: a requeued request
        # recomputes from prompt + generated, which must be complete
        self._drain_fetches_locked()
        for sid in sorted(set(e.sids)):
            slot = self.slots[sid] if 0 <= sid < self.S else None
            if slot is None:
                continue       # drain closed it, or already gone
            if sid == e.culprit_sid:
                self._teardown_slot_locked(sid, e.original,
                                           "fault_failed")
            else:
                self._requeue_after_fault_locked(sid, e)

    def _requeue_after_fault_locked(self, sid: int,
                                    e: EngineFault) -> None:
        """Requeue an innocent participant of a faulted dispatch,
        bounded: past ``max_retries`` attempts the request fails too
        (a poisoned batch must not retry forever). Tail of the queue
        — a faulting batch must not starve fresh arrivals — with
        exponential backoff gating re-admission."""
        slot = self.slots[sid]
        req = slot.req
        req.attempts += 1
        if req.attempts > self.max_retries:
            self._teardown_slot_locked(sid, RequestError(
                f"request {req.rid} failed after "
                f"{req.attempts - 1} retries (last fault: "
                f"{e.original!r})"), "retry_exhausted")
            return
        self.slots[sid] = None
        slot.preempted = True     # in-flight rows are recomputed
        self._free_slot_pages_locked(slot, retire=False)
        req.t_earliest = (time.monotonic() + self.retry_backoff_s
                          * (2 ** (req.attempts - 1)))
        self._wait.append(req)
        self.stats["retries"] += 1
        _metrics()["retries"].inc()
        self.events.append("requeue", rid=req.rid, sid=sid,
                           data={"attempts": req.attempts})

    def _plan_steps_locked(self) -> StepPlan:
        """Plan this round with the pure, device-free planner
        (serve/scheduler.py plan_step): which mid-prefill slots
        advance, a row of up to ``prefill_chunk`` tokens each, and
        how many decode steps ride behind them. Run-ahead-to-next-
        completion, quick cadence while admission work is pending,
        a step or two while prompts queue behind full prefill rows
        for longer than the riders last, and the eos bound all live in
        the planner — this wrapper only
        snapshots slot state (plus, with speculation on, one
        prompt-lookup proposal per seeded slot)."""
        if self.spec_len:
            self._propose_spec_locked()
        # Stale-frontier depth per slot: decode steps dispatched but
        # not yet read back (the overlapped loop plans BEFORE the
        # trailing drain reveals them). The planner uses it to cap
        # eos-bounded run-ahead so a late-revealed eos discards at
        # most one decode chunk per slot. Identity-checked against
        # the live slot: a freed-and-reseated slot's old rides are
        # not ITS staleness.
        stale = [0] * self.S
        for _buf, riders, steps in self._fetchq:
            for i, slot, _take in riders:
                if 0 <= i < self.S and self.slots[i] is slot:
                    stale[i] += steps
        # owed clamped at 0: an eos-mode rider can overshoot its
        # budget while emission trails, and cancelled/expired slots
        # are torn down before planning ever sees them — the planner
        # contract (serve/scheduler.py) is owed >= 0
        views = [SlotView(sid=i, admit_seq=s.admit_seq,
                          prompt_remaining=s.prefill_remaining,
                          owed=max(0, self._owed(s))
                          if s.cur is not None else 0,
                          seeded=s.cur is not None,
                          spec_drafts=len(s.spec_pending),
                          stale=stale[i],
                          pulling=s.pulling,
                          batch=s.req.batch)
                 for i, s in enumerate(self.slots) if s is not None]
        # Role admission knobs (disaggregation): a prefill replica
        # never runs ahead past one decode chunk, a decode replica's
        # prefill lane shrinks to residual-tail size. Read per round
        # so the pool can re-role a replica between requests.
        caps = role_plan_caps(self.role, page_size=self.Pg,
                              decode_chunk=self.K,
                              prefill_chunk=self.PC,
                              prefill_batch=self._max_prefill_batch,
                              max_run_ahead=self.KMAX)
        plan = plan_step(views, total_slots=self.S,
                         prefill_chunk=caps["prefill_chunk"],
                         decode_chunk=self.K,
                         max_run_ahead=caps["max_run_ahead"],
                         prefill_batch=caps["prefill_batch"],
                         eos_bounded=self.eos_id is not None,
                         spec_enabled=bool(self.spec_len))
        self.accounts.note_plan(
            plan, caps["prefill_batch"] * caps["prefill_chunk"])
        return plan

    def _propose_spec_locked(self):
        """Refresh each seeded slot's prompt-lookup proposal. In the
        lockstep loop this runs AFTER the round's full drain, so
        ``req.generated`` is exactly the device's token stream. In
        the overlapped loop it runs from the STALE frontier —
        ``req.generated`` may trail the device by up to one round's
        undrained chunks. That is safe by construction: proposals
        are hints the batched verify re-derives from the true argmax
        (a draft positioned against an outdated context simply gets
        rejected), and the proposer's monotonic-context contract
        (spec_decode.NGramIndex.sync) still holds because
        ``prompt + generated`` only ever grows. The proposer syncs
        its rolling index with the unseen tail and drafts up to
        ``spec_len`` continuation tokens. A slot whose remaining
        budget is 1 proposes nothing — the verify's bonus token
        already covers it."""
        for s in self.slots:
            if s is None:
                continue
            s.spec_pending = []
            if (s.cur is None or s.preempted or s.req.closed
                    or not s.req.generated):
                continue
            if s.spec is None:
                s.spec = self._proposer_factory()
            s.spec.sync(s.req.prompt + s.req.generated)
            room = min(self.spec_len, s.req.remaining - 1)
            if room > 0:
                s.spec_pending = [int(t) for t in s.spec.propose(room)]

    def _owed(self, slot: _Slot) -> int:
        """Decode STEPS this slot still needs, by dispatch-time
        arithmetic: the prefill emits token 1 of max_new_tokens, every
        ridden step emits one more token. Runs AHEAD of emission (which
        trails with the readbacks) — with an eos the true need may be
        less; emission then closes the request early. Of a model that
        decodes by blocks a step is a forward and this is a BOUND: the
        forwards its blocks cost at the most (``_Slot.forwards``) less
        those dispatched; exact under the schedules that reveal a fixed
        count a step, and under ``low_confidence_dynamic`` what a slot
        that finishes its blocks early leaves unused."""
        if slot.forwards is not None:
            return slot.forwards - slot.decoded
        return slot.req.max_new_tokens - 1 - slot.decoded

    def _retire_planned_locked(self):
        """No-eos mode: free slots whose budget of STEPS the dispatch
        just consumed — their tokens are still in flight (emission
        trails) but the SCHEDULE is deterministic, so the pages and the
        slot go back to the pool without waiting for a readback. (A
        block-decoding slot's budget is its bound in forwards: a slot
        that finished earlier was freed at the readback that showed its
        last block, ``_emit_to``.)"""
        for i, slot in enumerate(self.slots):
            if (slot is not None and slot.cur is not None
                    and self._owed(slot) <= 0):
                self.slots[i] = None
                self._free_slot_pages_locked(slot, retire=True)
                # "completed" counts at request close (emission)

    # ------------------------------------------------------- scheduler

    def _loop(self):
        while True:
            with self._work:
                while (not self._stopped and not self._wait
                       and not any(self.slots)
                       and not self._fetchq
                       and not self._pending_prefill
                       and self._pending_swap is None):
                    # a pending drain-mode weight swap is work: the
                    # settled engine must run one more round so the
                    # flip lands between rounds, not never
                    self._work.wait()
                if self._stopped:
                    # deliver every token already computed before
                    # exiting — retired slots' readbacks still trail;
                    # shutdown() then fails whatever remains in
                    # flight with EngineShutdown
                    self._drain_fetches_locked()
                    return
            # Hand the lock over between rounds. cancel(), shutdown(),
            # swap_weights() and submit() all wait on it, Python locks
            # are not fair, and a loop that re-takes the lock a few
            # bytecodes after dropping it beats a just-woken waiter
            # every time — a cancel could sit out a whole generation.
            time.sleep(0)
            try:
                self.step()
            except EngineFault as e:
                # attributable fault outside a dispatch section
                # (defensive — step() normally contains these)
                with self._lock:
                    self._contain_fault_locked(e)
            except BaseException as e:   # global: fail every request
                self._fail_all(e)
                return

    def _fail_all(self, e: BaseException):
        """Global failure (device loss, scheduler bug): every queued
        and in-flight request fails with the error. Attributable
        faults never reach here — they are contained per-slot in
        step() — so this is the path of last resort."""
        self.events.append("fail_all", data={"error": repr(e)})
        if self.flight_dir is not None:
            # the engine is about to lose everything it knows: dump
            # the postmortem BEFORE teardown clears the queues
            obs.dump_flight_bundle(self.flight_dir, "engine-fail-all",
                                   engine=self,
                                   extra={"error": repr(e)})
        with self._lock:
            self.stats["failed_all"] += 1
            failed = set()

            def fail(req):
                if req.closed or id(req) in failed:
                    return
                failed.add(id(req))
                req.closed = True
                req.error = e
                req.out_q.put(_DONE)

            for i, slot in enumerate(self.slots):
                if slot is not None:
                    fail(slot.req)
                    self.slots[i] = None
                    slot.preempted = True
                    self._free_slot_pages_locked(slot, retire=False)
            # retired-at-dispatch requests whose tokens were still in
            # flight live only in the readback queues
            for _buf, riders, _steps in self._fetchq:
                for _i, slot, _t in riders:
                    fail(slot.req)
            for _f, placements in self._pending_prefill:
                for _ix, slot, _row in placements:
                    fail(slot.req)
            self._fetchq.clear()
            self._pending_prefill.clear()
            for req in self._wait:
                fail(req)
            self._wait.clear()
            self._stopped = True

    def _next_admit_locked(self) -> Optional[_Request]:
        """Lane-aware head selection for admission. Drops closed
        requests parked at the head (cancelled/expired while queued
        by a path that left them in place — never admit), then picks
        the first ONLINE request anywhere in the queue: FIFO within
        each lane, but the online lane always outranks batch. Only
        when no online request waits does the batch head admit.

        The chosen request is rotated to the deque FRONT before
        returning, so every existing ``popleft`` admission path
        (plain admission, PULLING admission) stays correct without
        threading an index through."""
        while self._wait and self._wait[0].closed:
            self._wait.popleft()
        if not self._wait:
            return None
        head = self._wait[0]
        if not head.batch:
            return head
        # batch head: any live online request deeper in the queue
        # outranks it (closed entries are skipped in place — they
        # drop when they surface at the head)
        for k in range(1, len(self._wait)):
            r = self._wait[k]
            if r.closed or r.batch:
                continue
            del self._wait[k]
            self._wait.appendleft(r)
            return r
        return head

    def _victim_locked(self, exclude_sid: Optional[int] = None, *,
                       batch_only: bool = False) -> Optional[int]:
        """Preemption victim selection, one policy for every caller:
        the youngest occupied slot, with BATCH slots strictly before
        any online slot (bool sorts False < True, so the key
        ``(batch, admit_seq)`` under ``max`` is batch-first,
        youngest-first within the lane). ``exclude_sid`` protects
        the slot whose growth is hunting (never self-evict); PULLING
        slots are never victims (no pages to reclaim, and a
        background thread owns them). ``batch_only=True`` restricts
        the hunt to batch slots — the online-head admission path,
        where online slots must never be evicted to admit."""
        cands = (j for j, s in enumerate(self.slots)
                 if s is not None and not s.pulling
                 and j != exclude_sid
                 and (s.req.batch or not batch_only))
        return max(cands,
                   key=lambda j: (self.slots[j].req.batch,
                                  self.slots[j].admit_seq),
                   default=None)

    def _admit_locked(self):
        """Chunk-budget admission: a waiting request takes a free
        slot as soon as pages for its FIRST prefill chunk exist —
        not its whole prompt. The prompt then advances chunk by
        chunk in the scheduling rounds (no monolithic padded-batch
        prefill, no same-padded-length grouping: the chunked prefill
        call batches mixed lengths and offsets natively). FIFO:
        admission never reorders past the queue head.

        With the prefix cache on, admission first matches the longest
        cached page-aligned prefix: the slot's page table points at
        those shared pages read-only, prefill RESUMES at the matched
        offset (the existing mid-offset chunked-prefill path), and
        the slot's row of the prefill call only ever pays for the
        tokens actually computed — skipped tokens never enter
        ``prompt_remaining``. A fully-cached prompt copies its final
        matched page into a private page (COW: the model still needs
        the last position's logits to sample the first token, and
        that one-token re-prefill must not scatter into a shared
        page). When the pool is dry, refcount-0 cached pages are
        evicted LRU-first before admission gives up.

        A request carrying a router pull hint (``req.pull``) whose
        prefix is NOT locally cached admits in the PULLING phase
        instead: the slot is seated empty (no pages, no grants, the
        planner skips it) while a background thread pulls the prefix
        KV from the peer replica that advertised it
        (serve/kv_migration.py). Transfer completion inserts the
        pages into the prefix cache and requeues the request at the
        queue FRONT, so the next admission round admits it through
        THIS path as a plain local hit — mid-offset prefill resume,
        COW boundary handling, and hit accounting all unchanged. An
        aborted pull requeues without inserting anything: plain
        prefill, never a wedge.

        Priority lanes: the admitted head is the first ONLINE request
        anywhere in the queue; batch requests admit only when no
        online request waits (FIFO within each lane). When every slot
        is taken and the online head is blocked, the youngest BATCH
        slot is preempted on the spot — online traffic reclaims batch
        capacity slot-by-slot the moment it arrives. While an online
        head waits (for a slot or for pages), the lane order also
        guarantees no batch request can slip past it into capacity it
        frees."""
        if self._pending_swap is not None:
            # drain-mode weight swap pending: admission pauses so the
            # active set settles and the flip can land between rounds
            return
        while self._wait:
            req = self._next_admit_locked()
            if req is None:
                return
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                if not req.batch:
                    # online head blocked on a full batch: evict the
                    # youngest BATCH slot (recompute / prefix-cache
                    # resume on re-admission — token-identical) and
                    # retry. Online slots are never preempted for
                    # admission.
                    victim = self._victim_locked(None, batch_only=True)
                    if victim is not None:
                        self._preempt_locked(victim)
                        continue
                return
            if req.t_earliest and time.monotonic() < req.t_earliest:
                # retry backoff after a contained fault. FIFO is the
                # admission contract (per lane), so a backing-off
                # head delays everything behind it too.
                return
            prompt = req.recompute_prompt
            if req.pull is not None and self._try_pull_admit_locked(
                    free[0], req, prompt):
                continue       # PULLING slot seated; admit the rest
            shared_pages: List[int] = []
            matched = 0
            copy_src: Optional[int] = None
            if self.prefix_cache is not None:
                shared_pages, matched = self.prefix_cache.match(prompt)
                if matched and matched == len(prompt):
                    # whole prompt cached: re-prefill only the LAST
                    # token, into a private copy of the final page
                    copy_src = shared_pages.pop()
                    matched -= 1
            start = matched
            first = max(1, min(len(prompt) - start, self.PC))
            need = -(-(start + first) // self.Pg) - len(shared_pages)
            page_ids = self._alloc(need)
            if page_ids is None and self.prefix_cache is not None:
                # reclaim LRU refcount-0 cached pages before failing
                if self.prefix_cache.evict(
                        need - self.alloc.n_free) > 0:
                    page_ids = self._alloc(need)
            if page_ids is None:
                # pool dry: hand the matched references back and wait
                if self.prefix_cache is not None:
                    if copy_src is not None:
                        shared_pages = shared_pages + [copy_src]
                    if shared_pages:
                        self.prefix_cache.release(shared_pages)
                return         # wait for completions
            if copy_src is not None:
                # duplicate the boundary page on-stream before any
                # write can target it, then drop the borrowed ref
                self.pages = self._copy_page_fn(
                    self.pages, self._h2d(jnp.int32(copy_src)),
                    self._h2d(jnp.int32(page_ids[0])))
                self.prefix_cache.release([copy_src])
            self._wait.popleft()
            slot = _Slot(req=req, pages=shared_pages + page_ids,
                         pos=start, cur=None,
                         admit_seq=next(self._admit_seq),
                         prompt=prompt, prefilled=start,
                         # re-admission after preemption/fault-requeue:
                         # tokens already delivered count against the
                         # budget, or _owed() over-schedules by that
                         # many steps and run-ahead growth walks past
                         # max_seq_len (and the page-table width)
                         decoded=len(req.generated),
                         shared=len(shared_pages))
            self.slots[free[0]] = slot
            if self._block is not None:
                self._open_blocks_locked(free[0], slot)
            self.stats["admitted"] += 1
            _now = time.monotonic()
            self.events.append("admit", rid=req.rid, sid=free[0],
                               t=_now,
                               data={"cached": start,
                                     "pages": len(slot.pages)})
            if not req.generated \
                    and not req.attempts and not req.preemptions:
                # first admission only: re-admissions after
                # preemption/fault would double-count the wait
                obs.phase_metrics()["queue_wait"].observe(
                    max(0.0, _now - req.t_submit))
            if self.prefix_cache is not None:
                self.prefix_cache.account(start, len(prompt) - start)
                self.stats["cache_hit_tokens"] += start
                self.stats["cache_miss_tokens"] += len(prompt) - start
                if start:
                    self.stats["cache_hit_admissions"] += 1
                    self.events.append("cache_hit", rid=req.rid,
                                       sid=free[0], data=start)

    # ------------------------------------ a model that decodes by blocks

    def _open_blocks_locked(self, ix: int, slot: _Slot) -> None:
        """Admission of a request to a model that decodes by blocks:
        split the (recompute) prompt at its last whole block, set the
        books in blocks and forwards, and seed at once a slot whose
        prompt has no whole block to prefill. The prefill calls run the
        whole blocks; the remainder opens the first generated block
        beside masks. A re-admission after a preemption or a fault
        re-prefills ``prompt + emitted tokens`` the same way: past the
        first commit that is a whole number of blocks, and its K/V are
        the commits' (same mask, same tokens)."""
        bd = self._block
        L = bd.block_length
        whole = len(slot.prompt) // L * L
        slot.prompt, slot.tail = slot.prompt[:whole], slot.prompt[whole:]
        lead = len(slot.tail)
        blocks = -(-(lead + slot.req.remaining) // L)
        slot.end = whole + blocks * L
        slot.forwards = (bd.forwards(L - lead)
                         + (blocks - 1) * bd.forwards(L))
        slot.decoded = 0             # forwards of THIS admission
        if slot.prefill_remaining == 0:
            self._seed_blocks_locked([(ix, slot)])

    def _seed_blocks_locked(self, rows) -> None:
        """Seed the device's block state (``_jit_seed_blocks``) for
        ``rows`` [(slot index, slot)] whose whole prompt blocks are in
        their pages (at most a prefill call's rows): on-stream, no host
        sync; the slots ride the very next decode dispatch."""
        B, L = self._max_prefill_batch, self._block.block_length
        ixs = np.full((B,), self.S, np.int32)   # S = dropped row
        pos, left, lead = (np.zeros((B,), np.int32) for _ in range(3))
        blk = np.zeros((B, L), np.int32)
        for r, (ix, slot) in enumerate(rows):
            ixs[r], pos[r] = ix, len(slot.prompt)
            left[r], lead[r] = slot.req.remaining, len(slot.tail)
            blk[r, :lead[r]] = slot.tail
            slot.pos = len(slot.prompt)
            slot.cur = -1      # device-seeded: ridable
        self._dev_blocks = self._seed_fn(
            self._dev_blocks, *(self._h2d(a) for a in (
                ixs, pos, left, blk, lead)))

    def _most_committed(self, steps: int) -> int:
        """Positions the blocks ``steps`` forwards can commit at the
        most cover: a block costs at least two forwards (one that
        reveals, one that commits), and a dispatch may open on a
        commit."""
        return -(-steps // 2) * self._block.block_length

    def _write_end(self, slot: _Slot, steps: int) -> int:
        """The position below which a decode dispatch of ``steps`` steps
        writes this slot's pages, from the host's ``slot.pos``: a token
        a step; or, of a model that decodes by blocks, whose ``pos`` is
        the host's bound on the block's start, the end of the furthest
        block ``steps`` forwards can reach (a block costs at least two
        forwards, so they commit at most ``ceil(steps / 2)`` blocks and
        the last writes at most ``steps // 2`` blocks on), and never
        past the request's last block."""
        if self._block is None:
            return slot.pos + steps
        return min(slot.pos + (steps // 2 + 1) * self._block.block_length,
                   slot.end)

    # -------------------------------------------- KV migration (pull)

    def _try_pull_admit_locked(self, sid: int, req: _Request,
                               prompt: List[int]) -> bool:
        """PULLING admission: seat ``req`` in slot ``sid`` with no
        pages and spawn the background pull its router hint names.
        The hint is consumed EXACTLY ONCE (cleared before any check
        can bail), so no requeue path ever re-pulls. Declines — and
        falls through to normal admission — when no fetcher/cache is
        wired, the hint is empty, or the local tree already covers
        the advertised run (then the pull would buy nothing)."""
        pull = req.pull
        req.pull = None          # consumed exactly once
        if (self.kv_fetcher is None or self.prefix_cache is None
                or req.generated or self._stopped or self._draining):
            return False
        try:
            hashes = [int(h) for h in (pull.get("hashes") or ())]
        except (AttributeError, TypeError, ValueError):
            return False         # malformed hint: plain admission
        if not hashes:
            return False
        have, _ = self.prefix_cache.match_hashes(hashes)
        if have:
            self.prefix_cache.release(have)
        if len(have) >= len(hashes):
            return False         # local cache already covers the hint
        self._wait.popleft()
        slot = _Slot(req=req, pages=[], pos=0, cur=None,
                     admit_seq=next(self._admit_seq), prompt=prompt,
                     prefilled=0, decoded=len(req.generated),
                     pulling=True)
        self.slots[sid] = slot
        self.stats["kv_pull_admissions"] += 1
        self.events.append("pull_start", rid=req.rid, sid=sid,
                           data={"hashes": len(hashes),
                                 "local": len(have)})
        threading.Thread(target=self._run_pull,
                         args=(sid, slot, pull), daemon=True,
                         name=f"kv-pull-{req.rid}").start()
        return True

    def _run_pull(self, sid: int, slot: _Slot,
                  pull: Dict[str, Any]) -> None:
        """Background transfer for one PULLING slot, NO engine lock
        held: the injected fetcher runs the chunked pull protocol
        (kv_migration.pull_prefix — deadline, bounded retries, typed
        abort) against the donor. Landing and requeue happen back
        under the lock; any fetcher escape is an abort, never a
        wedge."""
        payload = None
        try:
            payload = self.kv_fetcher(pull)
        except Exception:
            payload = None
        with self._work:
            self._finish_pull_locked(sid, slot, payload)
            self._work.notify_all()

    def _finish_pull_locked(self, sid: int, slot: _Slot,
                            payload: Optional[Dict[str, Any]]) -> None:
        """Land a finished pull and requeue its request at the FRONT
        of the admission queue: the next ``_admit_locked`` admits it
        through the NORMAL path — a successful landing inserted the
        pulled pages into the prefix cache, so admission matches them
        as a local hit and resumes mid-offset prefill exactly like
        any cached prefix; a failed pull admits as plain prefill
        (fallback counted). Slot identity is validated first: cancel,
        deadline reap, shutdown, or preemption may have torn the slot
        down mid-transfer — the request's fate is already decided and
        this result is dropped."""
        if (self.slots[sid] is not slot or slot.preempted
                or not slot.pulling):
            return
        slot.pulling = False
        self.slots[sid] = None
        req = slot.req
        if req.closed or self._stopped:
            return
        landed = 0
        if payload is not None:
            landed = self._land_pulled_pages_locked(slot.prompt,
                                                    payload)
        if landed:
            self.stats["kv_pull_landed"] += 1
            self.events.append("pull_land", rid=req.rid, sid=sid,
                               data={"pages": landed,
                                     "wire_bytes":
                                         payload.get("wire_bytes", 0)})
        else:
            kv_migration.count_fallback(self.kv_migration_stats)
            self.stats["kv_pull_fallbacks"] += 1
            self.events.append("pull_fallback", rid=req.rid, sid=sid)
        self._wait.appendleft(req)   # front: admit before new arrivals

    def _land_pulled_pages_locked(self, prompt: List[int],
                                  payload: Dict[str, Any]) -> int:
        """Write pulled page payloads into freshly allocated pool
        pages and INSERT them into the prefix cache — the same
        radix-tree insert retirement uses, so refcounts, COW
        discipline, LRU order, and eviction see nothing new. Returns
        pages landed; 0 (mismatched/truncated payload, allocator dry)
        means fall back to plain prefill."""
        if (payload.get("kv_dtype") != self.kv_dtype
                or int(payload.get("page_size") or 0) != self.Pg
                or int(payload.get("n_layers") or 0)
                != self.cfg.n_layers):
            return 0
        n = min(int(payload.get("n_pages") or 0),
                len(prompt) // self.Pg)
        if n <= 0:
            return 0
        try:
            # decode + validate BEFORE allocating: a malformed
            # payload must not cost pool pages
            cols = [page_cols_from_bytes(self.cfg, self.Pg,
                                         self.kv_dtype, blobs)
                    for blobs in payload["pages"][:n]]
        except (ValueError, KeyError, TypeError):
            return 0
        page_ids = self._alloc(n)
        if page_ids is None and self.prefix_cache.evict(
                n - self.alloc.n_free) > 0:
            page_ids = self._alloc(n)
        if page_ids is None:
            return 0
        if self._write_page_fn is None:
            self._write_page_fn = self._track_program(
                _jit_write_page(self._mesh))
        for dst, page_cols in zip(page_ids, cols):
            self.pages = self._write_page_fn(
                self.pages, self._h2d(jnp.int32(dst)),
                [tuple(self._h2d(c) for c in layer)
                 for layer in page_cols])
        self.prefix_cache.insert(prompt[:n * self.Pg], page_ids, 0)
        self.stats["kv_pulled_pages"] += n
        return n

    # ------------------------------------------- KV migration (donor)

    def kv_pin_prefix(self, hashes: List[int]) -> List[int]:
        """Donor side of a cross-replica pull: resolve rolling path
        hashes to the longest resident page run and PIN it (refcount
        increment via ``PrefixCache.match_hashes``) so eviction can
        never yank a page mid-transfer. Caller owes one
        ``kv_release_pages`` for the run. Empty when the prefix is
        gone or the engine is stopped/draining — the KVDonor turns
        that into a typed ``KVPullAborted``."""
        with self._lock:
            if (self.prefix_cache is None or self._stopped
                    or self._draining):
                return []
            pages, _ = self.prefix_cache.match_hashes(hashes)
            return pages

    def kv_export_pages(self, pages: List[int]) -> List[Any]:
        """Raw bytes of pinned pages, per page per layer (int8 scales
        ride along — models/kv_cache.export_page_bytes). Under the
        engine lock: pool buffers are donated to jitted calls, so an
        unlocked read could touch an invalidated buffer mid-round.
        A stopped donor refuses with the typed abort — in-process
        pools must mirror what a dead peer process looks like over
        the socket, or chaos kills would "succeed" off a corpse.
        A model with recurrent layers exports nothing: its pages are
        not the whole of a request's state."""
        refuse_unsupported(self.cfg, kv_migration="export")
        with self._lock:
            if self._stopped:
                raise kv_migration.KVPullAborted(
                    "donor engine stopped mid-transfer")
            return [export_page_bytes(self.pages, int(p))
                    for p in pages]

    def kv_release_pages(self, pages: List[int]) -> None:
        """Unpin a transfer's pages (drop the match_hashes refs)."""
        with self._lock:
            if self.prefix_cache is not None and pages:
                self.prefix_cache.release(list(pages))

    def _dispatch_prefill_locked(self, grants):
        """Execute this round's prefill grants: grow each granted
        slot's pages to cover its chunk (evicting the youngest OTHER
        slot — batch lane first — when the pool runs dry, exactly
        like decode growth),
        then dispatch ONE batched chunked-prefill call for every
        surviving grant. Rows carry independent start offsets and
        lengths, so mixed prompt lengths and mid-prompt resumptions
        batch together."""
        rows = []
        for g in grants:
            slot = self.slots[g.sid]
            if slot is None:
                continue       # evicted by an earlier grant's growth
            take = min(g.tokens, slot.prefill_remaining)
            if take <= 0:
                continue
            self._fire("dispatch_prefill", sid=g.sid,
                       rid=slot.req.rid)
            self._check_cow_locked(slot, slot.prefilled)
            need = -(-(slot.prefilled + take) // self.Pg)
            evicted = False
            while len(slot.pages) < need:
                if self.slots[g.sid] is not slot:
                    evicted = True
                    break
                got = self._alloc(need - len(slot.pages))
                if got is not None:
                    slot.pages.extend(got)
                    break
                if (self.prefix_cache is not None
                        and self.prefix_cache.evict(
                            need - len(slot.pages)
                            - self.alloc.n_free) > 0):
                    continue    # reclaimed cached pages; retry alloc
                victim = self._victim_locked(g.sid)
                if victim is None:
                    # alone and still can't grow — attributable to
                    # THIS request: contained, not _fail_all
                    raise EngineFault(RequestError(
                        f"request {slot.req.rid}: page pool "
                        f"exhausted by one slot"),
                        culprit_sid=g.sid, culprit_rid=slot.req.rid)
                self._preempt_locked(victim)
            if not evicted and self.slots[g.sid] is slot:
                rows.append((g.sid, slot, take))
        # a LATER grant's growth can evict an EARLIER grant's slot
        # (victim choice is global youngest) — refilter before dispatch
        rows = [(ix, slot, take) for ix, slot, take in rows
                if self.slots[ix] is slot]
        if self._stopped:
            return     # force-killed mid-loop (zombie fence): the
                       # released thread must not dispatch
        if rows:
            self._prefill_batch(rows)

    def _grow_or_preempt_locked(self, steps: int):
        """Ensure every active slot's pages cover this dispatch's
        writes; evict the youngest slots (batch lane first) if the
        pool runs dry."""
        for i in sorted(
                (i for i, s in enumerate(self.slots) if s is not None),
                key=lambda i: self.slots[i].admit_seq):
            slot = self.slots[i]
            if slot is None:        # evicted by an elder slot's growth
                continue
            if slot.cur is None:
                continue        # not riding this dispatch (seed not
                                # yet scattered): writes nothing
            eff = min(steps, max(1, self._owed(slot)))
            need = -(-self._write_end(slot, eff) // self.Pg)
            while len(slot.pages) < need:
                if self.slots[i] is not slot:
                    # a preemption's drain closed THIS slot (eos /
                    # budget in a trailing readback); growing the
                    # detached object would leak its new pages
                    break
                got = self._alloc(need - len(slot.pages))
                if got is not None:
                    slot.pages.extend(got)
                    break
                if (self.prefix_cache is not None
                        and self.prefix_cache.evict(
                            need - len(slot.pages)
                            - self.alloc.n_free) > 0):
                    continue    # reclaimed cached pages; retry alloc
                victim = self._victim_locked(i)
                if victim is None:
                    # alone and still can't grow — attributable to
                    # THIS request: contained, not _fail_all
                    raise EngineFault(RequestError(
                        f"request {slot.req.rid}: page pool "
                        f"exhausted by one slot"),
                        culprit_sid=i, culprit_rid=slot.req.rid)
                self._preempt_locked(victim)

    def _check_cow_locked(self, slot: _Slot, write_pos: int) -> None:
        """Copy-on-write invariant: pool pages are donated to jitted
        calls and scattered into IN PLACE, so a write may only ever
        target a page the slot exclusively owns. Shared (cache-owned)
        pages are the slot's leading ``slot.shared`` page-table
        entries and must sit strictly behind the write frontier."""
        if slot.shared and write_pos < slot.shared * self.Pg:
            raise RuntimeError(
                f"COW violation: slot for rid={slot.req.rid} would "
                f"scatter at pos {write_pos} into shared page index "
                f"{write_pos // self.Pg} (< {slot.shared} cache-owned "
                f"pages)")

    def _free_slot_pages_locked(self, slot: _Slot,
                                *, retire: bool) -> None:
        """Return a slot's pages. Without the prefix cache this is a
        plain free. With it: shared pages only ever drop a reference
        (the tree keeps the KV); on retirement the finished prompt's
        full pages are INSERTED into the radix tree instead of freed
        (private ones donated, shared ones deduped), and only the
        boundary/generated tail goes back to the allocator."""
        if self.prefix_cache is None:
            self.alloc.free(slot.pages)
            return
        if retire and self._force_killed:
            # zombie fence: a force-killed engine's late retirement
            # must not publish pages into the prefix cache — drop
            # shared references and free private pages instead
            retire = False
        if retire:
            n_full = min(len(slot.prompt) // self.Pg, len(slot.pages))
            self.prefix_cache.insert(slot.prompt,
                                     slot.pages[:n_full], slot.shared)
            tail = slot.pages[n_full:]
            if tail:
                self.alloc.free(tail)
        else:
            self.prefix_cache.release(slot.pages[:slot.shared])
            priv = slot.pages[slot.shared:]
            if priv:
                self.alloc.free(priv)

    def prefix_stats(self) -> Optional[Dict[str, Any]]:
        """Prefix-cache counters (None when the cache is off)."""
        if self.prefix_cache is None:
            return None
        with self._lock:
            return self.prefix_cache.stats()

    def _preempt_locked(self, ix: int):
        # The victim's generated-so-far must be complete before the
        # recompute prompt is frozen: drain every trailing readback
        # (rare path — preemption already pays a full re-prefill).
        victim = self.slots[ix]
        self._drain_fetches_locked()
        if self.slots[ix] is not victim:
            # the drain closed the victim (eos / budget in a trailing
            # readback): its pages are already freed — nothing to evict
            return
        slot = victim
        self.slots[ix] = None
        slot.preempted = True     # in-flight rows are recomputed
        # retire=False: a preemption must NEVER free shared pages —
        # other sequences' page tables may point at them; their
        # references are dropped and the tree keeps the KV
        self._free_slot_pages_locked(slot, retire=False)
        slot.req.preemptions += 1
        self.stats["preemptions"] += 1
        if slot.req.batch:
            self.stats["batch_preemptions"] += 1
            _metrics()["batch_preempted"].inc()
        self.events.append("preempt", rid=slot.req.rid, sid=ix,
                           data={"preemptions": slot.req.preemptions,
                                 "lane": (LANE_BATCH if slot.req.batch
                                          else LANE_ONLINE)})
        self._wait.appendleft(slot.req)   # front: re-admit first

    def _dispatch_chunk_locked(self, steps: int):
        """Launch one decode dispatch of ``steps`` steps
        asynchronously. The full carry — pages, per-slot write
        position, per-slot next-token — lives on device and chains
        into the next dispatch; the host ships only the page table.
        The token buffer joins the trailing readback queue. ``steps``
        is a runtime scalar to the jitted fori_loop — no recompile
        per value."""
        pt = np.zeros((self.S, self.max_pages), np.int32)
        riders = []
        for i, slot in enumerate(self.slots):
            if slot is None or slot.cur is None:
                continue
            self._fire("dispatch_decode", sid=i, rid=slot.req.rid)
            self._check_cow_locked(slot, slot.pos)
            pt[i, :len(slot.pages)] = slot.pages
            # tokens this slot still owes its client from THIS
            # dispatch (the tail of an overshooting window is junk)
            take = min(steps, max(0, self._owed(slot)))
            riders.append((i, slot, take))
        if not riders or self._stopped:
            # every planned rider was preempted by this round's
            # prefill growth — an empty dispatch would decode junk —
            # or the engine was force-killed mid-loop (zombie fence)
            return
        if self._block is None:
            (toks, self.pages, self._rng, self._dev_pos,
             self._dev_cur, *moe) = self._decode_fn(
                self.params, self.pages, self._h2d(pt),
                self._dev_pos, self._dev_cur, self._rng,
                self._h2d(jnp.int32(steps)))
            moved = steps
        else:
            # steps are forwards; toks: (tokens, counts, reveal masks,
            # the dispatch's counters), read back together
            (toks, self.pages, self._rng, self._dev_blocks,
             *moe) = self._decode_fn(
                self.params, self.pages, self._h2d(pt),
                self._dev_blocks, self._rng,
                self._h2d(jnp.int32(steps)))
            # the bound on the block's start moves by the most blocks
            # the dispatch can commit; the readback puts it right
            moved = self._most_committed(steps)
        self._moe_pending.extend((v, True) for v in moe)
        # host mirrors advance NOW; emission trails
        for _i, slot, _t in riders:
            slot.pos += moved
            slot.decoded += steps
        self._fetchq.append((toks, riders, steps))
        # slot.pos already counts this dispatch
        self.accounts.note_decode(
            [self._write_end(slot, 0) for _i, slot, _t in riders], steps)
        self.events.append("decode", data=steps)
        self.stats["chunks"] += 1
        self.stats["decode_steps"] += steps
        self._hb = time.monotonic()   # dispatch completed: progress

    def _dispatch_spec_locked(self, grants):
        """One batched draft-and-verify dispatch (speculative
        decoding, serve/spec_decode.py). Every granted slot's row is
        ``[cur, d_1 .. d_k]`` — its last emitted token plus up to
        ``spec_len`` prompt-lookup drafts — scored in ONE forward
        pass through the paged multi-token branch at the slot's own
        offset (the same append-at-offset path chunked prefill uses).
        Row i's argmax at position j is the true greedy token after
        its j-th input token, so the longest draft prefix matching
        the argmax is accepted, plus the argmax after it (bonus
        token): between 1 and k+1 tokens per slot per dispatch, each
        one EXACTLY what non-speculative greedy decode would have
        produced.

        Rollback is free: the verify scattered KV for every input
        token, but positions past the accepted frontier hold tokens
        the model rejected — the slot's write offset is CLAMPED to
        ``pos + accepted + 1`` and the garbage beyond it is
        overwritten by later dispatches before any query's causal
        window can reach it (a key at position p is only attended
        once some query sits at >= p, and every later dispatch
        rewrites positions from the clamped frontier up before
        attending). Pages stay owned by the slot. COW discipline
        from the prefix cache is asserted per row: the verify writes
        from ``slot.pos``, which page-aligned matching keeps
        strictly past the shared (refcounted) pages, so
        verification never scatters into a page another sequence
        reads.

        Host-synchronous by construction: acceptance decides the
        next dispatch's input token and offset, so the argmax
        readback blocks here (spec trades the deferred pipeline's
        async pacing for multi-token dispatches). Under the
        overlapped loop the round's planning ran from the stale
        frontier, so the TRUE frontier is settled HERE instead —
        the verify's row 0 is ``generated[-1]``, which must be the
        device's latest token, not the host mirror's."""
        if self.overlap:
            # settle every trailing readback before freezing rows:
            # drafts proposed against the stale frontier are mere
            # hints (a mispositioned draft just gets rejected), but
            # the verify INPUT must be exact. This blocks only in
            # spec mode — the plain decode/prefill lanes never pay
            # it.
            self._drain_fetches_locked()
        T = self.spec_len + 1
        if self._verify_fn is None:
            self._verify_fn = self._track_program(
                _jit_verify(self.model, self._mesh))
        rows = []
        for g in grants:
            slot = self.slots[g.sid]
            if (slot is None or slot.cur is None
                    or not slot.req.generated):
                continue       # evicted / reseated since planning
            drafts = slot.spec_pending[:max(0, g.drafts)]
            self._fire("dispatch_spec", sid=g.sid, rid=slot.req.rid)
            self._check_cow_locked(slot, slot.pos)
            # grow pages to cover every verify write (cur + drafts),
            # exactly like prefill growth: prefix-cache eviction
            # first, then youngest-other (batch-first) preemption
            need = -(-(slot.pos + len(drafts) + 1) // self.Pg)
            evicted = False
            while len(slot.pages) < need:
                if self.slots[g.sid] is not slot:
                    evicted = True
                    break
                got = self._alloc(need - len(slot.pages))
                if got is not None:
                    slot.pages.extend(got)
                    break
                if (self.prefix_cache is not None
                        and self.prefix_cache.evict(
                            need - len(slot.pages)
                            - self.alloc.n_free) > 0):
                    continue
                victim = self._victim_locked(g.sid)
                if victim is None:
                    # submit() sized the pool for prompt+completion,
                    # and pos + drafts + 1 never exceeds that —
                    # attributable to THIS request, so contained
                    raise EngineFault(RequestError(
                        f"request {slot.req.rid}: page pool "
                        f"exhausted by one slot"),
                        culprit_sid=g.sid, culprit_rid=slot.req.rid)
                self._preempt_locked(victim)
            if not evicted and self.slots[g.sid] is slot:
                rows.append((g.sid, slot, drafts))
        # a later grant's growth can evict an earlier grant's slot
        rows = [(ix, slot, d) for ix, slot, d in rows
                if self.slots[ix] is slot]
        if not rows or self._stopped:
            return     # nothing to verify, or force-killed mid-loop
        ids = np.zeros((self.S, T), np.int32)
        start = np.zeros((self.S,), np.int32)
        pt = np.zeros((self.S, self.max_pages), np.int32)
        for i, slot, drafts in rows:
            ids[i, 0] = slot.req.generated[-1]
            if drafts:
                ids[i, 1:1 + len(drafts)] = drafts
            start[i] = slot.pos
            pt[i, :len(slot.pages)] = slot.pages
        out_dev, self.pages, *moe = self._verify_fn(
            self.params, self.pages, self._h2d(ids),
            self._h2d(start), self._h2d(pt))
        self._moe_pending.extend((v, True) for v in moe)
        out = np.asarray(out_dev)    # host sync: acceptance gates
        self._hb = time.monotonic()  # verify completed: progress
        self.accounts.note_decode(
            [slot.pos + T for _i, slot, _d in rows], 1, verify=True)
        m = spec_decode.metrics()
        self.stats["spec_rounds"] += 1
        # surviving slots' device decode state is reseeded with the
        # accepted frontier via the admission scatter (mode='drop'
        # rows padded with ix == S)
        ixs = np.full((self.S,), self.S, np.int32)
        toks = np.zeros((self.S,), np.int32)
        posv = np.zeros((self.S,), np.int32)
        n_seed = 0
        for i, slot, drafts in rows:
            row = out[i]
            a = 0
            while a < len(drafts) and drafts[a] == int(row[a]):
                a += 1
            produced = a + 1
            proposed = len(drafts)
            self.events.append("spec", rid=slot.req.rid, sid=i,
                               data=(proposed, a))
            self.stats["spec_riders"] += 1
            self.stats["spec_proposed"] += proposed
            self.stats["spec_accepted"] += a
            self.stats["spec_rejected"] += proposed - a
            self.stats["spec_tokens"] += produced
            if proposed:
                m["proposed"].inc(proposed)
                if a:
                    m["accepted"].inc(a)
                if proposed - a:
                    m["rejected"].inc(proposed - a)
                m["accept_rate"].observe(a / proposed)
            slot.spec_pending = []
            slot.pos += produced       # rollback clamp: KV frontier
            slot.decoded += produced   # = accepted + bonus, not k+1
            self._emit_to(slot.req, [int(t) for t in row[:produced]],
                          i)
            if self.slots[i] is slot:  # not closed by the emission
                ixs[n_seed] = i
                toks[n_seed] = int(row[a])
                posv[n_seed] = slot.pos
                n_seed += 1
        if n_seed:
            self._dev_cur, self._dev_pos = self._seed_fn(
                self._dev_cur, self._dev_pos, self._h2d(toks),
                self._h2d(ixs),
                self._h2d(jnp.arange(self.S, dtype=jnp.int32)),
                self._h2d(posv))

    def spec_stats(self) -> Optional[Dict[str, Any]]:
        """Speculative-decoding counters (None when speculation is
        off). ``tokens_per_dispatch`` is emitted tokens per
        (slot, verify-dispatch) ride — > 1.0 means speculation beat
        the one-token-per-forward-pass decode floor."""
        if not self.spec_len:
            return None
        with self._lock:
            s = self.stats
            proposed = s["spec_proposed"]
            riders = s["spec_riders"]
            return {
                "spec_len": self.spec_len,
                "spec_ngram": self.spec_ngram,
                "rounds": s["spec_rounds"],
                "proposed_tokens": proposed,
                "accepted_tokens": s["spec_accepted"],
                "rejected_tokens": s["spec_rejected"],
                "accept_rate": round(s["spec_accepted"] / proposed, 4)
                if proposed else 0.0,
                "tokens_per_dispatch":
                    round(s["spec_tokens"] / riders, 4)
                    if riders else 0.0,
            }

    def _drain_fetches_locked(self, limit: Optional[int] = None,
                              keep: int = 0,
                              ready_only: bool = False):
        """Trailing token readback: fetch up to ``limit`` outstanding
        decode buffers (None = all) plus EVERY in-flight prefill's
        firsts in one host sync each round, and emit to clients.
        Blocking here never stalls the device — the next dispatch is
        already queued behind the one being read."""
        blocking_rounds = 0
        while self._fetchq or self._pending_prefill:
            front_ready = bool(self._fetchq) and \
                _dev_ready(_first_leaf(self._fetchq[0][0]))
            # A finished buffer is always read (free — no block): on a
            # local device the previous dispatch is usually done by
            # now, so emission stays prompt. The `keep` fence only
            # protects STILL-COMPUTING dispatches — blocking on the
            # one just queued would serialize fetch after compute.
            take_buf = bool(self._fetchq) and (
                front_ready or
                (not ready_only and len(self._fetchq) > keep))
            # Prefill firsts ride along unless this is a ready-only
            # sweep and any of them is still computing (a sweep must
            # never block). Ordering stays safe: a rider's prefill is
            # always older than its first decode buffer, so a READY
            # front implies its riders' firsts are ready too — only
            # NEWER prefills (whose slots ride no fetched buffer yet)
            # can be withheld.
            pre_ready = bool(self._pending_prefill) and (
                not ready_only or all(
                    _dev_ready(_first_leaf(f))
                    for f, _ in self._pending_prefill))
            if not take_buf and not pre_ready:
                return
            if take_buf and not front_ready:
                if limit is not None and blocking_rounds >= limit:
                    return
                blocking_rounds += 1
            batch = []
            if take_buf:
                batch.append(self._fetchq.popleft())
            pend_pre = []
            if pre_ready:
                pend_pre, self._pending_prefill = \
                    self._pending_prefill, []
            _t_rb = time.monotonic()
            # Touch the heartbeat BEFORE the blocking get as well as
            # after: a drain working through several buffers blocks
            # once per buffer, and each iteration boundary is real
            # progress — without the pre-get touch a slow-but-moving
            # multi-buffer readback under load reads as one long
            # stall and rides the watchdog ladder to SUSPECT/WEDGED
            # (serve/watchdog.py judges heartbeat AGE, not activity).
            self._hb = _t_rb
            vals = jax.device_get(
                [b[0] for b in batch] + [f for f, _ in pend_pre])
            self._hb = time.monotonic()   # readback completed
            self._collect_moe_locked()
            self.events.append(
                "readback",
                data={"bufs": len(batch) + len(pend_pre)})
            obs.phase_metrics()["readback"].observe(self._hb - _t_rb)
            k = len(batch)
            # prefill firsts FIRST: a slot's seeding prefill always
            # precedes its first decode ride, and both can land in
            # the same drain round
            for (_f, placements), firsts in zip(pend_pre, vals[k:]):
                f_lps = None
                if isinstance(firsts, tuple):   # logprob capture
                    firsts, f_lps = firsts
                for ix, slot, row in placements:
                    if slot.preempted:
                        continue
                    try:
                        self._fire("readback", sid=ix,
                                   rid=slot.req.rid)
                    except EngineFault as e:
                        self._fail_rider_locked(ix, slot, e.original)
                        continue
                    self._emit_to(slot.req, [int(firsts[row])], ix,
                                  lps=(None if f_lps is None
                                       else [float(f_lps[row])]))
            for (_buf, riders, _steps), toks in zip(batch, vals):
                if self._block is not None:
                    self._emit_blocks_locked(riders, _steps, *toks)
                    continue
                lp_buf = None
                if isinstance(toks, tuple):     # logprob capture
                    toks, lp_buf = toks
                for i, slot, take in riders:
                    if slot.preempted:
                        continue    # recomputed from scratch
                    try:
                        self._fire("readback", sid=i,
                                   rid=slot.req.rid)
                    except EngineFault as e:
                        self._fail_rider_locked(i, slot, e.original)
                        continue
                    self._emit_to(slot.req, toks[:take, i].tolist(), i,
                                  lps=(None if lp_buf is None
                                       else lp_buf[:take, i].tolist()))

    def _emit_blocks_locked(self, riders, steps: int, buf, cnt, rev,
                            tally) -> None:
        """One decode dispatch of a model that decodes by blocks, read
        back: of each rider's forwards those with a count were commits,
        and their blocks' tokens go to the client together (``buf``
        [KMAX, S, L] from column 0, ``cnt`` [KMAX, S]); the host's bound
        on the block's start gives back the blocks the dispatch could
        have committed and did not. ``rev`` [KMAX, S] (the positions
        each forward revealed) is read only under ``record_reveals``."""
        self.accounts.fold_blocks(tally)
        L = self._block.block_length
        for i, slot, take in riders:
            if slot.preempted:
                continue    # recomputed from scratch
            try:
                self._fire("readback", sid=i, rid=slot.req.rid)
            except EngineFault as e:
                self._fail_rider_locked(i, slot, e.original)
                continue
            commits = np.flatnonzero(cnt[:take, i])
            slot.pos -= self._most_committed(steps) - len(commits) * L
            req = slot.req
            if req.reveal_steps is None:
                for f in commits:
                    self._emit_to(req, buf[f, i, :cnt[f, i]].tolist(), i)
                continue
            for f in range(take):
                if rev[f, i]:
                    slot.reveals.append(int(rev[f, i]))
                if not cnt[f, i]:
                    continue
                # the block's generated positions follow its prompt
                # remainder (the admission's first block alone has
                # one): each one's forward is the one whose mask holds
                # its bit
                lead, slot.tail = len(slot.tail), []
                req.reveal_steps.extend(
                    next(n for n, m in enumerate(slot.reveals)
                         if m >> p & 1)
                    for p in range(lead, lead + int(cnt[f, i])))
                slot.reveals = []
                self._emit_to(req, buf[f, i, :cnt[f, i]].tolist(), i)
                del req.reveal_steps[len(req.generated):]

    def _collect_moe_locked(self) -> None:
        """Hand the accounts the counters of every dispatch that has
        finished (a vector leaves its program with that dispatch's
        tokens: after a token readback this never waits)."""
        ready = []
        while self._moe_pending and _dev_ready(self._moe_pending[0][0]):
            ready.append(self._moe_pending.popleft())
        if ready:
            self.accounts.fold(jax.device_get([v for v, _d in ready]),
                               [d for _v, d in ready])

    def _fail_rider_locked(self, ix: int, slot: _Slot,
                           err: BaseException) -> None:
        """A fault while emitting ONE rider's tokens (readback/
        emission path) fails only that request: its slot — if still
        live; no-eos mode retires slots at dispatch time — is torn
        down, every other rider's emission proceeds untouched."""
        self.stats["contained_faults"] += 1
        _metrics()["contained_faults"].inc()
        if self.slots[ix] is slot and not slot.preempted:
            self._teardown_slot_locked(ix, err, "fault_failed")
        else:
            self._fail_req_locked(slot.req, err, "fault_failed")

    def _emit_to(self, req: _Request, tokens: List[int], ix: int,
                 lps: Optional[List[float]] = None):
        """Deliver tokens to the request; close it when it hits eos
        or its budget. In no-eos mode the slot/pages were already
        retired at dispatch time; with an eos, closing here frees
        them (the readback is what reveals the eos). ``lps`` (logprob
        capture) is index-aligned with ``tokens``; exactly the
        emitted prefix is appended, so eos/budget truncation keeps
        ``req.logprobs`` aligned with ``req.generated``."""
        if req.closed:
            return
        done = False
        n_put = 0
        for t in tokens:
            t = int(t)
            if req.t_first is None:
                # TTFT is stamped HERE — the moment the token reaches
                # the request stream — not when a later decode chunk
                # drains (the accounting bug the r05 bench carried)
                req.t_first = time.monotonic()
                ttft = req.t_first - req.t_submit
                if not req.batch:
                    # online SLO signals only: a batch request has no
                    # TTFT SLO (it may sit queued for hours by
                    # design), and folding its wait into ttfts_s /
                    # the EWMA would poison the autoscaler's latency
                    # signal and every bench percentile
                    self.ttfts_s.append(ttft)
                    a = self._ttft_ewma_alpha
                    self._ttft_ewma = ttft if self._ttft_ewma is None \
                        else a * ttft + (1 - a) * self._ttft_ewma
                self.events.append("first_token", rid=req.rid,
                                   sid=ix, t=req.t_first,
                                   data={"ttft_s": ttft,
                                         "lane": (LANE_BATCH
                                                  if req.batch
                                                  else LANE_ONLINE)})
                if not req.batch:
                    obs.phase_metrics()["ttft"].observe(ttft)
            req.generated.append(t)
            req.out_q.put(t)
            n_put += 1
            if ((self.eos_id is not None and t == self.eos_id)
                    or req.remaining <= 0):
                done = True
                break
        if n_put and req.logprobs is not None and lps is not None:
            req.logprobs.extend(float(x) for x in lps[:n_put])
        if n_put:
            _now = time.monotonic()
            self.events.append("emit", rid=req.rid, sid=ix, t=_now,
                               data={"n": n_put})
            if req.batch:
                self.stats["batch_tokens"] += n_put
                _metrics()["batch_tokens"].inc(n_put)
            if req.t_last_emit is not None:
                # mean gap per token over this readback batch
                gap = max(0.0, _now - req.t_last_emit) / n_put
                obs.phase_metrics()["inter_token"].observe(gap)
                if not req.batch:
                    # online lane only, like the TTFT EWMA: batch
                    # streams run at whatever cadence the backlog
                    # allows and would drown the decode pool's
                    # latency signal
                    a = self._itl_ewma_alpha
                    self._itl_ewma = gap if self._itl_ewma is None \
                        else a * gap + (1 - a) * self._itl_ewma
            req.t_last_emit = _now
        if done:
            req.closed = True
            slot = self.slots[ix]
            if slot is not None and slot.req is req:
                self.slots[ix] = None
                self._free_slot_pages_locked(slot, retire=True)
            self.stats["completed"] += 1
            self.events.append("retire", rid=req.rid, sid=ix,
                               data={"generated": len(req.generated)})
            req.out_q.put(_DONE)

    # ----------------------------------------------------- jitted fns

    def _prefill_batch(self, rows) -> None:
        """Dispatch ONE chunked-prefill call advancing up to
        ``_max_prefill_batch`` slots' prompts by their granted
        lengths. rows: [(slot index, slot, take), ...].

        Each row appends ``take`` prompt tokens AT ITS OWN OFFSET
        into its own pages (the paged-KV append-at-offset path:
        chunks start mid-page and span pages), so mixed lengths,
        mixed offsets, and resumed prompts share one executable —
        the old path compiled one executable per padded prompt
        length, measured as multi-second p99 stalls on cache misses.
        The chunk width is bucketed to a power of two (floor
        page_size, cap prefill_chunk): a handful of variants total.
        Rows whose chunk ENDS the prompt sample the request's first
        token from the chunk logits; it is seeded into the device
        decode state with an on-stream scatter (no host sync) and
        queued for emission at the next trailing readback — the
        first streamed token goes out at end-of-prompt-prefill,
        never after a decode-chunk drain. Unused batch rows point at
        the null page and are dropped by the seed scatter."""
        B = self._max_prefill_batch
        mx = max(take for _ix, _s, take in rows)
        T = max(1, min(self.PC, self.Pg))
        while T < mx:
            T *= 2
        T = min(T, self.PC)
        ids = np.zeros((B, T), np.int32)
        start = np.zeros((B,), np.int32)
        last_idx = np.zeros((B,), np.int32)
        pt = np.zeros((B, self.max_pages), np.int32)  # dummies -> null
        slot_ids = np.full((B,), self.S, np.int32)    # dummies -> none
        for r, (ix, slot, take) in enumerate(rows):
            ids[r, :take] = slot.prompt[
                slot.prefilled:slot.prefilled + take]
            start[r] = slot.prefilled
            last_idx[r] = take - 1
            pt[r, :len(slot.pages)] = slot.pages
            slot_ids[r] = ix
        out, self.pages, self._rng, *moe = self._prefill_fn(
            self.params, self.pages, self._h2d(ids),
            self._h2d(start), self._h2d(last_idx),
            self._h2d(pt), self._rng,
            # only a model with recurrent layers has a state a slot
            self._h2d(slot_ids) if self.state_bytes_per_slot else None)
        self._moe_pending.extend((v, False) for v in moe)
        # logprob capture packs (firsts, first_logprobs); the seed
        # scatter takes the raw firsts, emission gets the pair
        firsts = out[0] if self.capture_logprobs else out
        placements = []
        for r, (ix, slot, take) in enumerate(rows):
            slot.prefilled += take
            slot.pos = slot.prefilled
            if slot.prefill_remaining == 0:
                placements.append((ix, slot, r))
        # Seed the device decode state for rows that FINISHED their
        # prompt WITHOUT a host sync: scatter firsts/positions into
        # dev_cur/dev_pos rows on-stream, after which the slots ride
        # the very next decode dispatch.
        if self._block is not None:
            # no first token from prefill: the call's sample is not
            # read, the finished rows' block state is seeded, and
            # nothing of the call is emitted (its readback still syncs
            # drains and preemption barriers on it)
            self._seed_blocks_locked(
                [(ix, slot) for ix, slot, _row in placements])
            n_done, placements = len(placements), []
        else:
            ixs = np.full((B,), self.S, np.int32)   # S = dropped row
            rws = np.zeros((B,), np.int32)
            posv = np.zeros((B,), np.int32)
            for r, (ix, slot, row) in enumerate(placements):
                ixs[r], rws[r], posv[r] = ix, row, slot.pos
            self._dev_cur, self._dev_pos = self._seed_fn(
                self._dev_cur, self._dev_pos, firsts,
                self._h2d(ixs), self._h2d(rws), self._h2d(posv))
            for ix, slot, _row in placements:
                slot.cur = -1      # device-seeded: ridable
            n_done = len(placements)
        # firsts also stays on device for EMISSION: its readback
        # rides the next trailing sync, so prefill never stalls the
        # decode stream on a host RTT. Queued even with no finished
        # rows so drains (and preemption barriers) can sync on every
        # in-flight prefill dispatch.
        self._pending_prefill.append((out, placements))
        self.events.append(
            "prefill",
            rid=tuple(slot.req.rid for _ix, slot, _t in rows),
            data=tuple((ix, take) for ix, _s, take in rows))
        self.stats["prefills"] += 1
        self.accounts.note_prefill(
            start[:len(rows)], sum(take for _ix, _s, take in rows), B, T)
        self.stats["prefilled_seqs"] += n_done
        self._hb = time.monotonic()   # dispatch completed: a long
                                      # prompt prefilling chunk by
                                      # chunk is moving, not wedged
