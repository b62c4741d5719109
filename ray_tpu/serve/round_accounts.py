"""The round's accounts: what ``serve/engine.py``'s host loop reports of
a round, and nothing of the loop itself.

What a dispatch COUNTED ON THE DEVICE leaves its step program as one
int32 vector of the model's sections (models/mixtral.py
``stats_sections``): a section's layout, names and reading live beside
the function that lays it out, and here a vector is split by the
sections' lengths and named by their ``read``. What the host can say a
dispatch MUST HAVE READ it knows from its own positions, by kind of
cache; for what ONE layer's kernel visits, the kernels' own rules are
asked, of the shapes the layer hands them, under the program's mesh.
The engine owns one ``RoundAccounts``, hands it the vectors it may
read without waiting, and emits ``info`` and ``take()`` as the
``round`` event; the accounts add into the ``stats`` they are handed.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.kv_cache import (KIND_BORROWED, KIND_KV, KIND_LATENT,
                                     KIND_SLIDING, RecurrentState,
                                     SlidingRing, block_decode,
                                     has_latent_pages,
                                     kv_query_heads, latent_page_width,
                                     layer_kinds, page_layout,
                                     sampled_only_from,
                                     state_bytes_per_slot)
from ray_tpu.models.mixtral import stats_sections
from ray_tpu.ops import latent_window_attention as latent_window
from ray_tpu.ops import paged_decode_attention as paged_decode
from ray_tpu.ops import ring_window_attention as ring_window
from ray_tpu.ops import selective_scan
from ray_tpu.ops.paged_attention import paged_window_block_pages
from ray_tpu.serve.step_programs import BLOCK_COUNTERS, ambient_mesh


def _new_round_info() -> Dict[str, int]:
    """What a round dispatched, as its ``round`` event reports it.
    ``backlog`` is the planner's (serve/scheduler.py
    ``StepPlan.backlog``): mid-prefill slots the prefill call had no
    row for, counted only where that queue outlasts the riders; beside
    a non-zero ``decode_steps`` it says the round's decode was cut to
    ``BACKLOG_DECODE_STEPS`` (``stats["backlog_rounds"]`` counts those
    rounds). ``prefill_width`` is the ``T`` of the round's ``[rows, T]``
    prefill call (a power of two up to ``prefill_chunk``; 0 = no
    call): the shape a call's device time is grouped by.
    ``prefill_head_rows`` is the positions that call applied the
    model's output head to: its ``B``, one a row (the program asks the
    model for the logits of each row's ``last_idx`` alone,
    serve/step_programs.py ``_jit_prefill``), dummy rows included, where
    ``B x T`` went through the head before; 0 = no call.
    ``prefill_sampled_only_layers`` is the layers of that call that ran
    on those ``prefill_head_rows`` positions alone: the model's
    trailing layers that keep no entry (models/kv_cache.py
    ``sampled_only_from``; models/llama.py ``transformer_forward``
    narrows the call before the first of them), 0 for a model whose
    last layer keeps one, and without a call.
    ``decode_steps`` counts FORWARDS: of a model that decodes by blocks
    (models/kv_cache.py ``BlockDecode``) a step yields no token or a
    whole block's, and the ``denoise_*`` counters ``take`` adds say
    which (``fold_blocks``); its riders' positions below are the host's
    BOUND on each block's end, not a reading.
    ``decode_context_tokens`` is the sum over the decode dispatch's
    riders of their OWN context lengths after it (what each rider's
    last step attended), where ``decode_window_tokens`` is the longest
    rider's, rounded up to a block: the tokens a paged attention MUST
    read, beside those its block loop does. ``prefill_kernel_blocks``
    is 0 where the prefill program holds no kernel for its latent
    layers' attention (ops/latent_window_attention.py), else the key
    blocks ONE such layer's kernel visits over the call's live rows,
    each row to the block of its own last query.
    ``prefill_scan_kernel_positions`` is 0 where the prefill program
    holds no kernel for its state-space layers' scan
    (ops/selective_scan.py), else the positions ONE such layer's kernel
    walks over the call's rows: ``B x T``, dummy rows included, as the
    kernel does.
    ``decode_kernel_pages`` is 0 where the decode program holds no
    kernel for its K/V or latent layers' attention
    (ops/paged_decode_attention.py), else the pages ONE such layer's
    kernel visits at the dispatch's last step, each rider to its own
    last page: beside ``decode_riders`` x ``decode_window_tokens`` it
    says how far the visited pages sit from the block loop's."""
    return {"decode_riders": 0, "decode_steps": 0, "backlog": 0,
            "decode_window_tokens": 0, "decode_context_tokens": 0,
            "decode_kernel_pages": 0,
            "prefill_tokens": 0, "prefill_budget": 0,
            "prefill_rows": 0, "prefill_window_tokens": 0,
            "prefill_kernel_blocks": 0,
            "prefill_scan_kernel_positions": 0, "prefill_width": 0,
            "prefill_head_rows": 0, "prefill_sampled_only_layers": 0}


class RoundAccounts:
    """One engine's accounts. ``pool`` is its KV pool as built (a
    sliding layer's ring is read for shape and type, nothing is kept);
    ``slots``, ``page_size``, ``max_pages`` the decode program's shapes;
    ``kv_dtype`` and ``mesh`` are read again at every ask of a kernel."""

    def __init__(self, cfg, stats, pool, *, slots: int, page_size: int,
                 max_pages: int, kv_dtype: str, mesh):
        self.cfg, self.stats = cfg, stats
        self.S, self.Pg, self.max_pages = slots, page_size, max_pages
        self.kv_dtype, self.mesh = kv_dtype, mesh
        # the attention programs gather and attend a block of tokens
        # at a time (ops/paged_attention.py _paged_window_attention)
        self.window_block = page_size * paged_window_block_pages(
            page_size, max_pages)
        self.ring = next((jax.ShapeDtypeStruct(e.k.shape, e.k.dtype)
                          for e in pool if isinstance(e, SlidingRing)),
                         None)
        self.ring_len = self.ring.shape[2] if self.ring else 0
        self.state = next((jax.ShapeDtypeStruct(e.state.shape,
                                                e.state.dtype)
                           for e in pool if isinstance(e, RecurrentState)),
                          None)
        self.sliding_window = cfg.sliding_window if self.ring else 0
        self.state_by_slot = bool(state_bytes_per_slot(cfg, self.ring_len))
        # the layers that READ K/V pages where some keep none of their
        # own (models/kv_cache.py KIND_BORROWED): the owners and their
        # readers; 0 for a model whose every reader is its own owner
        kinds = layer_kinds(cfg)
        # the trailing layers that keep no entry: a prefill call runs
        # them at the one position a row it samples from
        self.sampled_only_layers = len(kinds) - sampled_only_from(cfg)
        self.page_readers = (kinds.count(KIND_KV) + kinds.count(
            KIND_BORROWED) if KIND_BORROWED in kinds else 0)
        # what the step programs count on the device, over live rows
        # only (() = nothing is returned, queued or reported), and each
        # section's running totals of its head, for ``load_report``
        self.sections = stats_sections(cfg)
        # a model that decodes by blocks: its decode program's own
        # counters (serve/step_programs.py BLOCK_COUNTERS), and the
        # queries a row its decode step asks a kernel's rule about
        self.block = block_decode(cfg)
        self.heads = [np.zeros((s.head,), np.int64) for s in self.sections]
        self.unreported = self._new_counts()
        self.begin_round()

    def begin_round(self) -> Dict[str, int]:
        """A new round's ``info``, written until its event takes it."""
        self.info = _new_round_info()
        return self.info

    def add(self, **counts: int) -> None:
        """Add ``counts`` to the ``round`` event and to the stats (a
        key only some models carry starts here)."""
        for key, n in counts.items():
            self.info[key] = self.info.get(key, 0) + n
            self.stats[key] += n

    # ------------------------------------- what the device counted

    def _new_counts(self) -> Dict[str, int]:
        """The sections' counters as the ``round`` event reports them
        (docs/serving.md): each under its section's prefix, and the
        decode dispatches' part under ``decode_`` behind it."""
        counts = {prefix + key: 0 for s in self.sections
                  for prefix in (s.prefix, s.prefix + "decode_")
                  for key in s.names}
        if self.block is not None:
            counts.update(("denoise_" + key, 0) for key in BLOCK_COUNTERS)
        return counts

    def fold(self, vectors, decode) -> None:
        """Add the host copies ``vectors`` of finished dispatches'
        counter vectors (``decode[i]``: vector i left a decode or a
        verify program) to the running totals and to what the next
        ``round`` event reports."""
        for vec, is_decode in zip(vectors, decode):
            at = 0
            for s, head in zip(self.sections, self.heads):
                part, at = vec[at:at + len(s)], at + len(s)
                head += part[:s.head]
                for key, value in s.read(part).items():
                    self.unreported[s.prefix + key] += value
                    if is_decode:
                        self.unreported[s.prefix + "decode_" + key] += value

    def fold_blocks(self, tally) -> None:
        """Add the host copy of a block program's ``BLOCK_COUNTERS``
        (one finished decode dispatch's, read with its tokens) to what
        the next ``round`` event reports as ``denoise_*``: forwards,
        commits, positions revealed, tokens emitted and forwards idled
        over the dispatches read back since the last event."""
        for key, n in zip(BLOCK_COUNTERS, tally):
            self.unreported["denoise_" + key] += int(n)

    def take(self) -> Dict[str, int]:
        """The counters gathered since the last ``round`` event, for
        this one: those of the dispatches whose results were read back
        meanwhile (under the overlapped loop, the round before's).
        Nothing for a model that counts nothing on the device."""
        out, self.unreported = self.unreported, self._new_counts()
        for k, v in out.items():
            self.stats[k] += v
        return out

    def load_report(self) -> Dict[str, Any]:
        """What the sections that keep a head report of its running
        totals (a mixture's routing so far), and a block program's
        counters. Nothing for a dense model that yields a token a step."""
        out = {k: v for s, head in zip(self.sections, self.heads)
               if s.head for k, v in s.load_report(head).items()}
        if self.block is not None:
            # a model that decodes by blocks: its block, and the block
            # program's counters so far (a step is a forward)
            out["block_length"] = self.block.block_length
            out.update(("denoise_" + key, self.stats.get(
                "denoise_" + key, 0)) for key in BLOCK_COUNTERS)
        return out

    # ------------------------- what the host knows a dispatch read

    def note_plan(self, plan, budget: int) -> None:
        """The round's plan (serve/scheduler.py ``StepPlan``) and what
        its prefill call could carry: its rows times a row's chunk. A
        backlog beside decode steps: prompts queue behind full rows and
        outlast the riders, and the planner cut this round's decode
        (the spec lane's one verify a round is not a cut)."""
        self.info["prefill_budget"] = budget
        self.info["backlog"] = plan.backlog
        if plan.backlog and plan.decode_steps:
            self.stats["backlog_rounds"] += 1

    def note_window(self, key: str, end: int) -> None:
        """Record under ``key`` the positions a dispatch's paged
        attention gathers and attends when its longest live row's last
        query sits at ``end - 1``: ``end`` rounded up to whole blocks,
        inside the table's width. The round event keeps the round's
        widest, ``stats`` the sum over dispatches. The host knows every
        row's position, so this costs no readback."""
        blk = self.window_block
        window = min(-(-end // blk) * blk, self.max_pages * self.Pg)
        self.info[key] = max(self.info[key], window)
        self.stats[key] += window

    def note_state_slots(self, n: int) -> None:
        """``n`` slots' recurrent state was advanced by a dispatch (a
        prefill call's rows, a decode call's riders): the ``round``
        event's and the stats' ``state_slots``. Nothing for a model
        that keeps none."""
        if self.state_by_slot:
            self.add(state_slots=n)

    def note_prefill(self, starts, granted: int, B: int, T: int) -> None:
        """A ``[B, T]`` prefill call was dispatched whose live rows
        begin at ``starts`` and hold ``granted`` prompt tokens."""
        self.add(prefill_rows=len(starts), prefill_head_rows=B,
                 prefill_sampled_only_layers=self.sampled_only_layers,
                 prefill_tokens=granted)
        self.info["prefill_width"] = T
        self.note_state_slots(len(starts))
        # every row's queries run to start + T, padding and all
        self.note_window("prefill_window_tokens", int(max(starts)) + T)
        if self.prefill_kernel_serves(T):
            self.add(prefill_kernel_blocks=latent_window.kernel_blocks(
                starts, T, self.window_block,
                -(-self.max_pages * self.Pg // self.window_block)))
        self.add(prefill_scan_kernel_positions=B * T
                 if self.prefill_scan_serves(T) else 0)

    def note_decode(self, ends, steps: int, verify: bool = False) -> None:
        """A decode dispatch of ``steps`` steps was launched whose
        riders' LAST queries sit at ``ends`` less one (the program
        widens the window step by step; of a model that decodes by
        blocks, the end of the block the dispatch closes on: what its
        last query sees); ``verify``: it was one spec-verify forward
        over rows that end there. Of a model with
        sliding-window layers also ``decode_sliding_keys``: the riders'
        contexts each cut at the window, the keys ONE sliding layer's
        last step has to score. Of a model whose pages have readers
        beside their owner also ``decode_shared_kv_reads``: the riders'
        context entries times the layers that READ pages, the entries
        the last step's attention has to fetch over all of them."""
        ends = [int(e) for e in ends]
        if not verify:
            self.note_state_slots(len(ends))
        self.info["decode_riders"] = len(ends)
        self.info["decode_steps"] = steps
        self.note_window("decode_window_tokens", max(ends))
        self.add(decode_context_tokens=sum(ends))
        if self.page_readers:
            self.add(decode_shared_kv_reads=sum(ends) * self.page_readers)
        if self.sliding_window:
            self.add(
                decode_sliding_keys=sum(min(e, self.sliding_window)
                                        for e in ends),
                sliding_kernel_keys=self.ring_kernel_keys(len(ends)))
        if not verify and self.decode_kernel_serves():
            self.add(decode_kernel_pages=paged_decode.kernel_pages(
                ends, self.Pg, self.max_pages))

    # ------------------------------ the questions asked of kernels

    def prefill_kernel_serves(self, T: int) -> bool:
        """Whether the ``[rows, T]`` prefill program's latent layers
        attend through the kernel: the question
        ``_paged_window_attention`` asks of the same shapes, under the
        mesh the program is traced under."""
        cfg = self.cfg
        with ambient_mesh(self.mesh):
            return has_latent_pages(cfg) and latent_window.serves(
                T, cfg.n_heads, latent_page_width(cfg), cfg.kv_lora_rank,
                self.Pg, cfg.dtype)

    def prefill_scan_serves(self, T: int) -> bool:
        """Whether the ``[rows, T]`` prefill program's state-space
        layers scan through the kernel: the question ``ssm_chunked``
        asks, of a chunk of ``T`` positions and one layer's states as
        the pool keeps them (a delta-rule layer's state has another
        shape, which the rule refuses), under the mesh the program is
        traced under."""
        with ambient_mesh(self.mesh):
            return self.state is not None and selective_scan.serves(
                T, self.state)

    def decode_kernel_serves(self) -> bool:
        """Whether the decode program's paged layers (the latent ones
        where the model has them, else the K/V ones) attend through the
        kernel: ``paged_decode.applies``, the very question
        ``_paged_window_attention`` asks, of a decode step's queries
        and one layer's pages as ``page_layout`` stores them (what the
        pool is built from: its type and int8 scales are read there,
        not decided again here), under the mesh the program is traced
        under."""
        cfg = self.cfg
        latent = has_latent_pages(cfg)
        if not latent and KIND_KV not in layer_kinds(cfg):
            return False
        # a page without its pass axis, if any: [Pg, KH, D] of K and of
        # V (and an int8 pool's scales), or a latent pool's one [Pg, W]
        k, v, sk = ([
            jax.ShapeDtypeStruct((1,) + shape[-(2 if latent else 3):],
                                 dtype)
            for shape, dtype in page_layout(
                cfg, KIND_LATENT if latent else KIND_KV, self.Pg,
                self.kv_dtype)] + [None, None])[:3]
        # a head's query is as wide as what it is scored against: a KV
        # head's key, or (absorbed) a stored latent entry, whose value
        # is its latent; and the layer hands the kernel a whole group
        # of query heads for every head ROW the page stores, the rows
        # that pad it among them (models/olmo_hybrid.py: 30 heads
        # stored, and so asked, as 32)
        heads = (cfg.n_heads if latent else k.shape[-2] * (
            kv_query_heads(cfg, KIND_KV) // cfg.n_kv_heads))
        # (a block program's step asks with a whole block a row, under
        # the block's mask)
        T = 1 if self.block is None else self.block.block_length
        q = jax.ShapeDtypeStruct((self.S, T, heads, k.shape[-1]),
                                 cfg.dtype)
        table = jax.ShapeDtypeStruct((self.S, self.max_pages), jnp.int32)
        with ambient_mesh(self.mesh):
            return paged_decode.applies(
                q, k, v, sk, table, cfg.kv_lora_rank if latent else None,
                T)

    def ring_kernel_keys(self, riders: int) -> int:
        """``sliding_kernel_keys`` of a decode dispatch of ``riders``:
        the ring positions ONE sliding layer's kernel
        (ops/ring_window_attention.py) fetches for them, beside
        ``decode_sliding_keys`` (the riders' windows, what must be
        read); 0 where the decode program holds the ``jax.numpy`` form.
        ``ring_window.applies``, the very question the layer asks, of a
        decode step's queries and new keys and one layer's rings as the
        pool stores them, under the mesh the program is traced under."""
        cfg, ring = self.cfg, self.ring
        q, k = (jax.ShapeDtypeStruct((self.S, 1, heads, cfg.head_dim),
                                     cfg.dtype)
                for heads in (kv_query_heads(cfg, KIND_SLIDING),
                              cfg.n_kv_heads))
        with ambient_mesh(self.mesh):
            serves = ring_window.applies(q, k, k, ring, ring,
                                         self.sliding_window)
        return ring_window.kernel_keys(riders, self.ring_len) if serves \
            else 0
