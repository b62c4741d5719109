"""Device-free closed-loop replay of the pure planner.

``plan_step`` is driven as the engine drives it, round after round,
by a saturating closed loop (64 clients on 32 slots, as the
benchmark's ``*-sat`` cells) under FIXED costs a prefill call and a
decode step: no device, no clock but the replay's own. What a round
does to the slots is the engine's arithmetic (serve/engine.py): a
grant advances a row's prompt, a row that ends its prompt is seeded
and rides the SAME round's decode dispatch, a rider retires when the
dispatch consumes its budget, and its client comes back a round later
(the trailing readback).

What it holds the planner to: under a prefill backlog that outlasts
the riders (long prompts, more slots mid-prompt than the call has
rows) the decode batch fills and the rows stay full; where the queue
clears before the riders leave (a ramp filling empty slots with short
prompts, rows that keep up with admission) the rule never engages, in
the ramp either, and the schedule is the parent's round for round.
"""
import collections
import dataclasses

import pytest

from ray_tpu.serve.scheduler import (BACKLOG_DECODE_STEPS, SlotView,
                                     plan_step)

SLOTS, CLIENTS = 32, 64
ROWS, PREFILL_CHUNK, DECODE_CHUNK, MAX_RUN_AHEAD = 4, 256, 8, 128


def _parent_plan(views, **kw):
    """The cadence before the backlog rule: a round that grants a
    prefill row decodes ``decode_chunk`` steps whoever queues."""
    plan = plan_step(views, **kw)
    if plan.backlog and plan.decode_steps:
        plan = dataclasses.replace(plan, decode_steps=DECODE_CHUNK)
    return plan


def _prefill_ms(window_tokens):
    # a [4, 256] call whose block loop runs to its longest row's window
    return 33.0 + 80.0 * window_tokens / 8192


def _decode_step_ms(riders):
    # weights once, a block loop over every slot, the riders' experts
    return 8.0 + 0.15 * riders


def replay(prompt_len, output_len, rounds, plan=plan_step,
           prefill_ms=_prefill_ms, decode_step_ms=_decode_step_ms,
           stagger_ms=15.0):
    """Run ``rounds`` rounds; returns one dict a round. Clients start
    ``stagger_ms`` apart, as the benchmark's do."""
    slots = [None] * SLOTS
    starting = collections.deque(i * stagger_ms for i in range(CLIENTS))
    queued = returning = 0    # clients waiting; clients whose last
                              # tokens trail a round
    seq, clock, log = 0, 0.0, []
    for _ in range(rounds):
        while starting and starting[0] <= clock:
            starting.popleft()
            queued += 1
        if not queued and not any(slots):
            clock = starting[0]
            continue
        for i in range(SLOTS):
            if slots[i] is None and queued:
                queued -= 1
                slots[i] = {"admit_seq": seq, "prefilled": 0,
                            "owed": 0, "seeded": False}
                seq += 1
        views = [SlotView(sid=i, admit_seq=s["admit_seq"],
                          prompt_remaining=prompt_len - s["prefilled"],
                          owed=s["owed"], seeded=s["seeded"])
                 for i, s in enumerate(slots) if s is not None]
        p = plan(views, total_slots=SLOTS, prefill_chunk=PREFILL_CHUNK,
                 decode_chunk=DECODE_CHUNK, max_run_ahead=MAX_RUN_AHEAD,
                 prefill_batch=ROWS, eos_bounded=False)
        ms, tokens = 0.0, 0
        if p.prefill:
            ms += prefill_ms(max(slots[g.sid]["prefilled"]
                                  for g in p.prefill) + PREFILL_CHUNK)
            for g in p.prefill:
                s = slots[g.sid]
                s["prefilled"] += g.tokens
                if s["prefilled"] == prompt_len:
                    s["seeded"], s["owed"] = True, output_len - 1
                    tokens += 1
        riders = 0
        queued, returning = queued + returning, 0
        if p.decode_steps:
            for i, s in enumerate(slots):
                if s is None or not s["seeded"]:
                    continue
                riders += 1
                tokens += min(p.decode_steps, s["owed"])
                s["owed"] -= p.decode_steps
                if s["owed"] <= 0:
                    slots[i] = None
                    returning += 1
            ms += p.decode_steps * decode_step_ms(riders)
        clock += ms
        log.append({"rows": len(p.prefill), "steps": p.decode_steps,
                    "riders": riders, "backlog": p.backlog,
                    "tokens": tokens, "ms": ms})
    return log


def _summary(log):
    decoding = [r for r in log if r["steps"]]
    prefilling = [r for r in log if r["rows"]]
    return {
        "riders": sum(r["riders"] for r in decoding) / len(decoding),
        "rows": sum(r["rows"] for r in prefilling) / len(prefilling),
        "steps": sum(r["steps"] for r in decoding) / len(decoding),
        "cut_share": (sum(1 for r in decoding if r["backlog"])
                      / len(decoding)),
        "tokens_per_s": (sum(r["tokens"] for r in log)
                         / sum(r["ms"] for r in log) * 1e3),
    }


@pytest.fixture(scope="module")
def longdoc():
    """8,192 in (32 chunks), 512 out: the ``longdoc-sat`` shape. The
    first 400 rounds are the ramp (the first cohorts' 32 rounds of
    prefill, then the batch filling)."""
    return (_summary(replay(8192, 512, 1600)[400:]),
            _summary(replay(8192, 512, 1600, plan=_parent_plan)[400:]))


def test_longdoc_backlog_fills_the_decode_batch(longdoc):
    """A step or two a round while prompts queue for a row: the riders
    are most of the batch and the rows stay full."""
    change, _parent = longdoc
    assert change["riders"] >= 20, change
    assert change["rows"] >= 3.9, change
    assert BACKLOG_DECODE_STEPS <= change["steps"] < 4.0, change
    assert 0.5 < change["cut_share"] < 0.95, change


def test_longdoc_parent_cadence_rides_a_quarter_of_the_batch(longdoc):
    """The replay reproduces what the chip read before the rule
    (``decode_riders_mean`` 8.0 of 32, ``prefill_rows_mean`` 4.0), so
    what it says of the rule is said of the same system."""
    _change, parent = longdoc
    assert parent["riders"] == pytest.approx(8.0, abs=0.5), parent
    assert parent["rows"] >= 3.9, parent
    assert parent["steps"] == DECODE_CHUNK


def test_longdoc_backlog_rule_wins_under_the_same_costs(longdoc):
    change, parent = longdoc
    assert change["tokens_per_s"] > 1.25 * parent["tokens_per_s"], (
        change, parent)


@pytest.mark.parametrize("prompt_len,output_len", [
    (256, 96),      # ``chat-sat``: one chunk a prompt
    (1024, 256),    # ``doc-sat``: rows at 0.89 of capacity
])
def test_rule_never_engages_where_the_queue_clears_first(
        prompt_len, output_len):
    """Shapes whose rows keep up with admission. The ramp fills 32
    empty slots within a few rounds, so up to 28 prompts queue for
    rows then; but they need fewer rounds of prefill (7 and 28) than
    the first riders have rounds of decode (12 and 32): the queue
    joins today's riders whatever the cadence, the rule stays out, and
    the whole schedule, ramp and all, is the parent's."""
    log = replay(prompt_len, output_len, 1200)
    parent = replay(prompt_len, output_len, 1200, plan=_parent_plan)
    queued = [r for r in parent[:100] if r["backlog"] or r["rows"] == 4]
    assert queued                     # prompts did queue in the ramp
    assert not any(r["backlog"] and r["steps"] for r in log)
    assert log == parent
    assert _summary(log[100:])["riders"] >= 28
