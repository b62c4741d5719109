"""Pure chunked-prefill + decode step planner for the LLM engine.

One scheduling round of the continuous-batching engine
(serve/engine.py) is planned here, device-free: given a host-side
snapshot of the slots, decide (a) which mid-prefill slots advance and
by how many prompt tokens — one row of the prefill program each, up
to ``prefill_chunk`` tokens a row, up to ``prefill_batch`` rows — and
(b) how many decode steps to dispatch in the SAME round. The engine
dispatches the prefill chunk first and the decode chunk immediately
behind it, both asynchronously, so the device pipeline interleaves
``P D P D P D ...`` — decode never stalls for a whole prompt the way
monolithic padded-batch prefill stalls it (the r05 161ms-TTFT /
1.63x-throughput shape this module exists to fix).

Pure and deterministic on purpose: tier-1 CPU tests drive
``plan_step`` directly with synthetic ``SlotView`` snapshots and
assert the interleaving/budget/run-ahead properties without touching
a device (the same reason the reference keeps its scheduling policy
separate from its raylet I/O).

Policy, in order:

- Prefill grants: mid-prefill slots in lane-then-admission order
  (online lane first, FIFO within each lane — admission never
  reorders within a lane, so neither does prefill) each receive
  ``min(prompt_remaining, prefill_chunk)`` tokens until the prefill
  program's rows (``prefill_batch``) run out. The unit of the budget
  is what the device is charged for: the prefill call is
  ``[prefill_batch, T]`` wide whatever rides in it, so a round's
  budget is ``prefill_batch x prefill_chunk`` tokens and every row
  the program computes carries a prompt when one waits. A long
  prompt holds ONE row for ``ceil(len / prefill_chunk)`` rounds and
  the prompts behind it proceed in the other rows; with one slot
  mid-prefill the round grants it one chunk, as a single shared
  chunk would. ``prompt_remaining`` is net of any tokens the prefix
  cache (serve/prefix_cache.py) satisfied at admission — a
  cache-hit slot enters mid-prompt, so its row only ever pays for
  tokens actually computed.
- Decode steps: if any seeded slot exists, decode rides every round.
  While admission work is pending (a free slot, an unseeded slot, a
  prefill grant this round) the cadence stays at ``decode_chunk`` so
  new arrivals join promptly and prefill chunks interleave; with a
  full, fully-seeded batch the plan runs ahead to the next completion
  event (min owed over riders) exactly as before (a step is whatever
  the engine's decode program makes of it: a token a rider, or a
  forward of a rider's block for a model that decodes by blocks, whose
  ``owed`` is a bound in forwards). With an eos the
  run-ahead is bounded — tokens past an unpredicted eos are wasted.
  Under the engine's OVERLAPPED loop the views may trail the device
  frontier (``SlotView.stale``: dispatched-but-undrained steps); any
  stale eos-bounded rider tightens the cap to one ``decode_chunk``,
  which bounds the worst-case discard on a late-revealed eos to one
  chunk per slot.
- Decode cadence follows the prefill backlog: when, after the grants,
  a mid-prefill slot was left WITHOUT a row (the rows are full and
  prompts queue behind them) AND the prompts in slots need more rounds
  of prefill than the riders have rounds of decode left, the plain
  decode lane dispatches ``BACKLOG_DECODE_STEPS`` steps, not
  ``decode_chunk``. A request holds a row for ``len / prefill_chunk``
  rounds and then rides ``tokens / steps`` rounds: with long prompts
  and ``decode_chunk`` steps a round today's riders are gone before
  the queued prompts reach the batch, so the batch stays a fraction
  full for as long as prompts queue, and every decode step of such a
  round delays the rows the queue waits for. Both sides are counted in
  rounds from what the views hold: the prefill chunks of every
  mid-prompt slot over the rows, against the riders' mean ``owed``
  over ``decode_chunk``. Where the queue clears before the riders
  leave (a ramp filling empty slots with short prompts; rows that keep
  up with admission) the queued prompts join today's riders anyway and
  the cadence stays what it was. It is the policy chunked-prefill
  servers ship (a token or two an iteration beside the prefill
  budget); ``decode_chunk`` remains the cadence otherwise. What it
  trades: under backlog a rider's tokens come two a round instead of
  ``decode_chunk`` a (longer) round, and a queued prompt reaches its
  first token sooner. Decode still rides every round, so a rider's gap
  stays bounded by one round. Two exceptions, both read from the
  arguments: a queued BATCH-lane prompt counts only when no seeded
  slot is online (batch work never slows online riders), and a lane of
  ONE row counts no backlog at all — that is the residue lane
  ``role_plan_caps`` leaves a decode-role replica, whose cadence must
  not drop because fallback prompts crawl through it.
  ``StepPlan.backlog`` carries the count.
- Priority lanes (``SlotView.batch``, serve/batch_tier.py): offline
  batch slots share the round with online traffic but never crowd it.
  Prefill grants order ONLINE slots first (FIFO within the lane),
  batch slots take whatever rows are left — a deep batch backlog can
  never delay an online prompt's next chunk by more than the chunk
  already in flight. Decode is lane-blind by design: a seeded batch
  slot rides the same dispatch as everyone else (evicting it saves
  nothing once its KV is resident — preemption happens in the engine
  when pages or slots are actually contended, batch-first).
- Spec lane (``spec_enabled``, serve/spec_decode.py): when any seeded
  slot carries draft tokens this round, ONE batched verify dispatch
  replaces the decode chunk — every seeded slot rides it (a slot with
  zero drafts degrades to a plain one-token step inside the same
  dispatch), so speculation never forks the device schedule. Draft
  counts are clamped so a verify can never emit past a slot's
  remaining budget (``owed``) nor past ``max_run_ahead`` (spec rounds
  count against the same run-ahead ceiling as decode). When NO slot
  has a proposal the round degrades to the plain decode lane — held
  to quick cadence, since running ahead would decode past every
  future proposal window — and the prefill lane is computed first
  either way: speculation never starves chunked prefill.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

# Device-count-agnosticism CONTRACT, test-enforced
# (tests/test_scheduler_guard.py): the planner may import nothing
# beyond this list — in particular never jax / jaxlib / numpy — and
# never reads device topology. One StepPlan must drive a 1-chip engine
# and an N-way tensor-parallel engine identically; the moment a device
# count leaks in here, sharded and unsharded replicas plan different
# rounds and token parity dies.
ALLOWED_IMPORTS = frozenset({"__future__", "dataclasses", "typing"})

# Priority lanes: every request carries one of these through
# admission, planning, and preemption. ONLINE is the latency-critical
# default; BATCH marks preemptible offline work (serve/batch_tier.py)
# that soaks idle capacity and yields it slot-by-slot the moment
# online traffic arrives.
LANE_ONLINE = "online"
LANE_BATCH = "batch"

# Replica roles for prefill/decode disaggregation
# (serve/engine_pool.py). UNIFIED is the classic mixed replica;
# PREFILL replicas take new prompts and hand finished prefills to the
# decode pool over the KV-migration path; DECODE replicas own the
# token streams after handoff. Pure data: the role changes nothing in
# ``plan_step`` itself — it only selects the knob clamps below, which
# the engine applies to the arguments it passes in.
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLE_UNIFIED = "unified"
REPLICA_ROLES = frozenset({ROLE_PREFILL, ROLE_DECODE, ROLE_UNIFIED})


def role_plan_caps(role, *, page_size, decode_chunk, prefill_chunk,
                   prefill_batch, max_run_ahead):
    """Role-adjusted planner knobs, pure data in -> data out.

    - ``prefill``: refuses decode-phase growth. Run-ahead is clamped
      to one decode chunk so a prefill replica never commits long
      decode dispatches: its steady state is prompt chunks plus the
      single bridging token each handoff needs, and anything longer
      only delays the next waiting prompt (exactly the interference
      disaggregation exists to remove).
    - ``decode``: skips the prefill lane. The round's prefill lane
      collapses to ONE row of one page plus one token — enough to
      absorb a handoff's residual tail (``len(prompt) mod page_size``
      plus the bridging token always fits one round) and to crawl
      through a full plain prefill when a fallback or chaos resubmit
      lands here (correct, just slow — a hard refusal would strand
      exactly the recovery paths that must keep working). The one-row
      lane is also what keeps this role's decode cadence: the planner
      counts no prefill backlog behind a single row, so prompts
      crawling through the residue lane never cut a round's decode.
    - ``unified``: knobs pass through untouched.

    Unknown roles raise: a typo'd role silently planning as unified
    would erase the disaggregation it was meant to configure.
    """
    if role not in REPLICA_ROLES:
        raise ValueError(
            f"unknown replica role {role!r}; expected one of "
            f"{sorted(REPLICA_ROLES)}")
    caps = {"prefill_chunk": prefill_chunk,
            "prefill_batch": prefill_batch,
            "max_run_ahead": max_run_ahead}
    if role == ROLE_PREFILL:
        caps["max_run_ahead"] = max(1, min(max_run_ahead,
                                           decode_chunk))
    elif role == ROLE_DECODE:
        caps["prefill_chunk"] = max(1, min(prefill_chunk,
                                           page_size + 1))
        caps["prefill_batch"] = 1
    return caps

# Named knob presets for the two serving regimes. Pure data (the
# import guard above applies): the engine/deployment layer maps these
# onto its constructor knobs; the planner itself reads nothing here.
# ``prefill_chunk`` is prompt tokens a ROW a round: a round's prefill
# call carries up to ``prefill_batch`` rows of it (the engine's program
# is four rows wide) and costs the same whether one row or all of them
# hold a prompt, so a round may grant 4 x ``prefill_chunk`` tokens.
#
# - ``latency``: the defaults the online path has always run —
#   short decode cadence, bounded admission queue, moderate prefill
#   chunks so TTFT stays flat under interleave.
# - ``throughput``: offline batch inference with no TTFT SLO — deep
#   (unbounded) admission queue, large prefill chunks so prompt
#   processing amortizes dispatch overhead, longer decode run-ahead.
#   ``max_queued=None`` is deliberate: the batch driver bounds its own
#   in-flight window (serve/batch_tier.py), so the engine queue depth
#   is the driver's concurrency knob, not a shed boundary.
SCHEDULER_PROFILES = {
    "latency": {
        "decode_chunk": 4,
        "prefill_chunk": 256,
        "max_run_ahead": 256,
        "max_queued": 2,
    },
    "throughput": {
        "decode_chunk": 16,
        "prefill_chunk": 512,
        "max_run_ahead": 512,
        "max_queued": None,
    },
}


def scheduler_profile(name):
    """Knob preset for ``name`` ('latency' | 'throughput'): a fresh
    dict the caller may mutate. Unknown names raise — a silently
    defaulted profile would hide a typo'd deployment config."""
    try:
        return dict(SCHEDULER_PROFILES[name])
    except KeyError:
        raise ValueError(
            f"unknown scheduler profile {name!r}; expected one of "
            f"{sorted(SCHEDULER_PROFILES)}") from None


@dataclasses.dataclass(frozen=True)
class SlotView:
    """Host snapshot of one occupied slot, as the planner sees it."""
    sid: int                 # slot index
    admit_seq: int           # admission order (FIFO fairness)
    prompt_remaining: int    # prompt tokens not yet prefilled
    owed: int                # decode steps still owed (seeded slots).
                             # Steps, not tokens: of a model that
                             # decodes by blocks (models/kv_cache.py
                             # BlockDecode) a step is a forward, and
                             # this is the engine's BOUND, blocks left
                             # x (denoising steps + 1) less the
                             # forwards dispatched; a slot that
                             # finishes its blocks earlier is retired
                             # by the engine at the readback that
                             # shows it, and the run-ahead and backlog
                             # rules below read the bound as they read
                             # any other slot's steps
    seeded: bool             # riding decode dispatches already
    spec_drafts: int = 0     # draft tokens proposed this round
                             # (prompt-lookup, serve/spec_decode.py)
    stale: int = 0           # decode steps dispatched but not yet
                             # read back: under the engine's
                             # overlapped loop the view may TRAIL the
                             # device frontier by up to one round —
                             # this is the depth of that trail. 0
                             # under the lockstep loop (the pre-plan
                             # drain settles everything).
    pulling: bool = False    # PULLING phase: a cross-replica KV
                             # prefix pull is in flight for this slot
                             # (serve/kv_migration.py). It holds the
                             # slot so admission order is preserved,
                             # but must receive NO prefill grant —
                             # its prompt either lands from the pull
                             # or requeues for plain prefill. Unseeded
                             # by construction, so the quick-cadence
                             # rule already treats it as pending
                             # admission work.
    batch: bool = False      # BATCH lane (priority=LANE_BATCH):
                             # preemptible offline work. Prefill
                             # grants order online slots first; the
                             # engine preempts batch slots before any
                             # online slot when pages or slots run
                             # dry.

    @property
    def prefilling(self) -> bool:
        return self.prompt_remaining > 0 and not self.pulling


@dataclasses.dataclass(frozen=True)
class PrefillGrant:
    sid: int
    tokens: int


@dataclasses.dataclass(frozen=True)
class SpecGrant:
    """One slot's ride on this round's batched verify dispatch.
    ``drafts`` is the number of proposed tokens to verify — 0 means
    the slot rides as a plain one-token step (its row is just
    [cur])."""
    sid: int
    drafts: int


@dataclasses.dataclass(frozen=True)
class StepPlan:
    prefill: Tuple[PrefillGrant, ...]
    decode_steps: int
    spec: Tuple[SpecGrant, ...] = ()
    backlog: int = 0         # mid-prefill slots left without a row
                             # this round that the decode cadence
                             # answers to (``_prefill_backlog``):
                             # non-zero cuts the plain decode lane to
                             # BACKLOG_DECODE_STEPS

    @property
    def idle(self) -> bool:
        return (not self.prefill and self.decode_steps == 0
                and not self.spec)


# Decode steps of a round that leaves prompts queuing behind full
# prefill rows for longer than the riders last (``plan_step``, "Decode
# cadence follows the prefill backlog"). A constant, not a knob: on the
# chip, over 160 s of 8,192-token prompts, 1 and 2 read level (604 and
# 608 tokens/s) and inside a 40 s window 2 read ahead (546 and 581): a
# closed loop's slots swing between prefill-heavy and decode-heavy
# phases, less deeply when a rider keeps two steps a round (PERF.md
# section 6, PR 35).
BACKLOG_DECODE_STEPS = 2


def _prefill_backlog(waiting, seeded, *, prefill_chunk, prefill_batch,
                     decode_chunk) -> int:
    """Mid-prefill slots left without a row this round that the decode
    cadence answers to. ``waiting``: the mid-prefill views in grant
    order (the first ``prefill_batch`` hold the rows); ``seeded``: the
    riders. 0 unless prompts queue behind full rows AND the queue
    outlasts the riders: the prefill rounds every mid-prompt slot
    still needs (chunks over rows) exceed the decode rounds the riders
    have left (mean ``owed`` over ``decode_chunk``). A batch-lane
    prompt counts only when no rider is online; behind a lane of one
    row (a decode-role replica's residue lane) nothing counts."""
    if prefill_batch < 2:
        return 0
    if any(not v.batch for v in seeded):
        waiting = [v for v in waiting if not v.batch]
    unserved = len(waiting) - prefill_batch
    if unserved <= 0 or not seeded:
        return max(0, unserved)
    chunks = sum(-(-v.prompt_remaining // prefill_chunk) for v in waiting)
    owed = sum(max(0, v.owed) for v in seeded)
    # chunks / prefill_batch > owed / len(seeded) / decode_chunk
    outlasts = (chunks * decode_chunk * len(seeded)
                > owed * prefill_batch)
    return unserved if outlasts else 0


def plan_step(slots: Sequence[SlotView], *, total_slots: int,
              prefill_chunk: int, decode_chunk: int,
              max_run_ahead: int, prefill_batch: int,
              eos_bounded: bool,
              spec_enabled: bool = False) -> StepPlan:
    """Plan one scheduling round. Pure: no device, no clock, no
    engine state — everything it needs is in the arguments.

    slots: occupied slots only (free slots are ``total_slots`` minus
    ``len(slots)``). Returns the prefill grants (lane order then
    FIFO, one row of up to ``prefill_chunk`` tokens each, at most
    ``prefill_batch`` rows) and either the decode step count (0 = no
    decode dispatch) or, when
    ``spec_enabled`` and any seeded slot proposed drafts, the spec
    grants for one batched verify dispatch (decode_steps is then 0 —
    the lanes are exclusive per round).
    """
    if prefill_chunk < 1:
        raise ValueError("prefill_chunk must be >= 1")
    if decode_chunk < 1:
        raise ValueError("decode_chunk must be >= 1")

    # Lane-ordered prefill: every online slot (FIFO) ahead of every
    # batch slot (FIFO) — a deep batch backlog mid-prefill must never
    # take the row an online prompt's next chunk needs. bool sorts
    # False < True, so (batch, admit_seq) is exactly that order. Each
    # grant is one row of the prefill call, which computes all
    # ``prefill_batch`` rows whatever they hold: there is no token cap
    # shared between rows.
    waiting = sorted((v for v in slots if v.prefilling),
                     key=lambda v: (v.batch, v.admit_seq))
    grants = [PrefillGrant(v.sid,
                           min(v.prompt_remaining, prefill_chunk))
              for v in waiting[:prefill_batch]]

    seeded = sorted((v for v in slots if v.seeded),
                    key=lambda v: v.admit_seq)
    backlog = _prefill_backlog(
        waiting, seeded, prefill_chunk=prefill_chunk,
        prefill_batch=prefill_batch, decode_chunk=decode_chunk)
    if not seeded:
        return StepPlan(tuple(grants), 0, backlog=backlog)

    if spec_enabled and any(v.spec_drafts > 0 for v in seeded):
        # Spec lane: ONE batched verify covering every seeded slot
        # (zero-draft rows are plain one-token steps), replacing this
        # round's decode chunk. A verify emits between 1 and
        # drafts + 1 tokens per slot, so drafts are clamped to the
        # slot's remaining budget minus the guaranteed bonus token
        # and to the run-ahead ceiling the decode lane honors.
        spec = tuple(
            SpecGrant(v.sid, max(0, min(v.spec_drafts, v.owed - 1,
                                        max_run_ahead - 1)))
            for v in seeded)
        return StepPlan(tuple(grants), 0, spec, backlog)

    # Defensive clamp: cancelled/expired slots are torn down before
    # the engine snapshots views, so they never appear here at all —
    # but an eos-mode rider's owed can still arrive negative (decoded
    # past budget while emission trails) and must not drag min(rem)
    # below the 1-step floor.
    rem = [max(0, v.owed) for v in seeded]
    quick = (len(slots) < total_slots
             or any(not v.seeded for v in slots)
             or bool(grants))
    # Spec mode keeps the decode lane on quick cadence even with a
    # full batch: run-ahead would decode past every future proposal
    # window before the host proposer gets another round (speculation
    # trades run-ahead pipelining for multi-token dispatches).
    steps = (decode_chunk if quick or spec_enabled
             else max(decode_chunk, min(rem)))
    if backlog:
        # The rows are full and the queue behind them outlasts the
        # riders: every decode step of this round delays the row a
        # queued prompt waits for, and the decode batch is short of
        # exactly those prompts. A step or two keeps the riders moving.
        steps = min(steps, BACKLOG_DECODE_STEPS)
    if eos_bounded:
        steps = min(steps, 2 * decode_chunk)
        if any(v.stale > 0 for v in seeded):
            # Stale-frontier discard bound (overlapped loop): a rider
            # with undrained steps may already be past its eos
            # without the host knowing. Capping the next dispatch at
            # ONE decode chunk — together with the engine's trailing
            # drain, which blocks once the pipeline is two dispatches
            # deep — bounds the tokens ever discarded on a
            # late-revealed eos to at most one decode chunk per slot.
            steps = min(steps, decode_chunk)
    return StepPlan(tuple(grants), max(1, min(steps, max_run_ahead)),
                    backlog=backlog)
