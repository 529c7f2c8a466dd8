"""How many decode entries the engine loop keeps in the device's queue,
and how many steps each of them runs.

`pipeline_depth` is the most the loop will queue. What it queues is worked
out once a loop turn from two times the loop takes on itself anyway:

  host turn     what the host does between one decode read's return and
                the next one's start: the emit of the entry just read, the
                prefill entries read behind it, admission, the enqueues.
                Taken from the step ledger's records (`turn_parts`): wall
                less the waits on the device (`device_sync`). The time an
                enqueue call stands blocked in the runtime (`dispatch`
                wall over its CPU) is IN it: on a v5e it is as long with
                two entries queued as with four (PERF.md section 6, PR
                42), so it is the enqueue's own latency, not the sign of
                a full queue, and the next entry reaches the device that
                much later whoever is to blame. The upper quartile of the
                recent turns.
  device step   the time between the returns of two decode reads in a row
                that both had to wait, the first with a decode entry still
                queued behind it, over the steps of the block the second
                one read: the device was busy from the one return to the
                other, with one decode entry and the prefills queued
                before it. A step's time, because full and half blocks
                mix and an entry is as long as its block. The LOWER
                quartile of the recent ones: the intervals that held no
                prefill.

The device needs the next entry no later than the end of what is queued:
with d decode entries in flight a read's return leaves it d - 1, so the
host's turn has to fit into d - 1 entries, and

    depth = clip(1 + ceil(k x host_turn / (device_step x block)),
                 min(2, cap), cap)

for the block the turn dispatches. Which block that is, the same rule
says (a block is as short as the host can feed):

    half  `decode_block_size // 2` steps while a request waits to be
          admitted, so that the read it waits behind comes sooner; and
          whenever the depth asked about a HALF block stays under the
          cap: the host keeps the device fed at half blocks with room to
          spare, so whoever arrives next waits out half of what it would
    full  `decode_block_size` steps otherwise

The rule stands at the cap, and the block is a full one unless a request
waits, wherever it cannot know better: without both estimates (the first
turns after a start or a reset), under an admission plane (a rank's own
clock may not choose a program: every rank has to dispatch the same
ones), and after the queue ran dry, which drops both estimates: a decode
read with no decode entry queued behind it while slots decode, or every
entry the depth keeps queued found done at its read, two reads in a row
at the least (one such read is one stall of the host; at a depth of two
and half blocks any stall longer than a half block makes one, and eight
turns of full blocks at the cap would cost the prompts of the next
second more than the stall cost the device). No setting, no environment
variable and no model's or cell's name enters it.
"""

from __future__ import annotations

import collections
import math
from typing import Any, Dict, Optional, Tuple

# k: how far a host turn's tail stands over the value the rule is handed
# (the upper quartile of the recent turns). Chip readings (PR 42, a v5e,
# 45 s windows): the longest return-to-next-read of a window over the
# window's upper quartile was 2.64 in `chat-open` (3.51 in a run with
# two host stalls of 130 ms), 1.16 in `decode-closed`, 1.84 in solar's
# cell; PERF.md section 6 (PR 42).
TAIL_OVER_TYPICAL = 3.0

RING = 32            # recent turns and steps an estimate is taken over
MIN_SAMPLES = 8      # fewer, and there is no estimate
# a read that came back sooner than this found its entry done: the copy
# to the host was started at the enqueue, so it is a memcpy of a block's
# tokens (tens of microseconds, a few milliseconds where the server's
# threads hold the interpreter), where a wait is a good part of a block.
# A read that waited less had a margin this thin: it counts as none
WAITED_S = 2e-3

# why a decode block ran the steps it did (`engine.queue` -> `blocks`)
FULL, HALF_WAITS, HALF_ROOM = "full", "half_request_waits", "half_host_room"


def depth_for(host_turn_s: Optional[float], device_entry_s: Optional[float],
              cap: int, *, mirrored: bool = False) -> int:
    """The rule, as a function of what the loop observed. `mirrored`: the
    deque is mirrored state under an admission plane."""
    if mirrored or not host_turn_s or not device_entry_s:
        return cap
    need = 1 + math.ceil(TAIL_OVER_TYPICAL * host_turn_s / device_entry_s)
    return max(min(2, cap), min(cap, need))


def turn_parts(rec) -> Tuple[float, float]:
    """(before, after) of a step record's host seconds: what the loop
    thread did up to the record's read, and from its return on (`demux`,
    `emit`). The wait on the device is in neither; the gap since the last
    record's end (its close, an idle iteration) is in `before`."""
    seg = rec.segments
    after = seg.get("demux", 0.0) + seg.get("emit", 0.0)
    before = (rec.idle_gap_s + rec.wall_s - seg.get("device_sync", 0.0)
              - after)
    return max(0.0, before), after


def _estimate(ring, share: float) -> Optional[float]:
    """The `share` quantile of a ring; None while it holds too few."""
    if len(ring) < MIN_SAMPLES:
        return None
    ordered = sorted(ring)
    return ordered[min(len(ordered) - 1, int(share * len(ordered)))]


class QueueDepth:
    """The loop's running estimates, and the depth and the block they
    give. Written by the loop thread alone; `snapshot` (`/debug/engine` ->
    `engine.queue`) reads plain numbers and dicts whose keys are all there
    from the start, so it takes no lock."""

    def __init__(self, cap: int, mirrored: bool = False, block: int = 1):
        self.cap = max(1, int(cap))
        self.mirrored = bool(mirrored)
        # the two blocks warm-up compiles (`decode_block_size` and half of
        # it), and no other
        self.full = max(1, int(block))
        self.half = max(1, self.full // 2)
        self.turns_by_depth: Dict[int, int] = {
            depth: 0 for depth in range(min(2, self.cap), self.cap + 1)}
        self.blocks: Dict[str, int] = {FULL: 0, HALF_WAITS: 0, HALF_ROOM: 0}
        self.resets_by_dry_sync = 0
        self.reset()

    def reset(self) -> None:
        """A start or a device-state reset: no estimate, so the cap and,
        unless a request waits, the full block."""
        self._turns: "collections.deque" = collections.deque(maxlen=RING)
        self._steps: "collections.deque" = collections.deque(maxlen=RING)
        self._turn_s = 0.0          # the open turn's host seconds so far
        self._turn_broken = True    # ... which no decode read's return began
        self._returned_at: Optional[float] = None
        self._found_done = 0        # decode reads in a row that did not wait
        self.host_turn_s: Optional[float] = None
        self.device_step_s: Optional[float] = None
        self.host_has_room = False
        self.block_now = self.full
        self.depth_now = self.cap

    # -- what the loop observed -------------------------------------------------
    def note_record(self, rec) -> None:
        """A closed step record. A decode or verify record ends a turn at
        its read and begins the next at the read's return; a prefill
        record (read behind a decode entry, in the same loop turn) and a
        record without a read lie inside one."""
        before, after = turn_parts(rec)
        if rec.phase in ("decode", "verify"):
            if not self._turn_broken:
                self._turns.append(self._turn_s + before)
            self._turn_s, self._turn_broken = after, False
        else:
            self._turn_s += before + after

    def note_park(self) -> None:
        """The loop waited for work with nothing in flight: what the
        ledger will show as the next record's gap is no host turn."""
        self._turn_broken = True

    def note_entry(self, seconds: float, steps: int = 1) -> None:
        """One decode entry's time on the device, a block of `steps`."""
        self._steps.append(seconds / max(1, steps))

    def note_read(self, returned_at: float, waited_s: float,
                  queued_behind: int, steps: int = 1) -> None:
        """A decode read's return: `waited_s` it blocked, `queued_behind`
        decode entries the deque still held, `steps` the block it read."""
        waited = waited_s >= WAITED_S
        if waited and self._returned_at is not None:
            self.note_entry(returned_at - self._returned_at, steps)
        self._returned_at = (returned_at if waited and queued_behind
                             else None)
        self._found_done = 0 if waited else self._found_done + 1
        # every entry this depth keeps behind the one being read was done
        # before the host came for it: the device had nothing left. Two
        # such reads in a row at the least (the module's docstring; on a
        # v5e, PR 47: 12 in eight `chat-open` windows, 10 of them alone,
        # each right after a read that came back 80-160 ms late)
        if self.depth_now < self.cap \
                and self._found_done >= max(2, self.depth_now - 1):
            self.ran_dry()

    def note_break(self) -> None:
        """A read that is no decode block's (a verify: one at a time, the
        device idle behind it): the next decode read's return is no
        entry's end after the last one's."""
        self._returned_at = None

    def ran_dry(self) -> None:
        """The device was left without a decode entry while slots decode:
        the rule was wrong a moment ago. Both estimates start again, so
        the cap stands until the loop has seen `MIN_SAMPLES` more turns
        and entries, and the depth comes down again only as they allow."""
        if self.depth_now < self.cap:
            self.resets_by_dry_sync += 1
        self.reset()

    # -- the depth and the block ------------------------------------------------
    def _depth_of(self, steps: int) -> int:
        """The depth that keeps the device fed with blocks of `steps`."""
        step_s = self.device_step_s
        return depth_for(self.host_turn_s, step_s and step_s * steps,
                         self.cap, mirrored=self.mirrored)

    def block(self, waits: bool) -> Tuple[int, str]:
        """(steps, why) of a decode block dispatched now; `waits`: a
        request waits to be admitted."""
        if self.half < self.full:
            if waits:
                return self.half, HALF_WAITS
            if self.host_has_room:
                return self.half, HALF_ROOM
        return self.full, FULL

    def dispatched(self, waits: bool) -> int:
        """The steps of the block the loop is dispatching, counted under
        why it has them."""
        steps, why = self.block(waits)
        self.blocks[why] += 1
        return steps

    def turn(self, waits: bool = False) -> int:
        """Work out this turn's block and the depth that goes with it,
        and count the turn under the depth."""
        self.host_turn_s = _estimate(self._turns, 0.75)
        self.device_step_s = _estimate(self._steps, 0.25)
        self.host_has_room = self._depth_of(self.half) < self.cap
        self.block_now = self.block(waits)[0]
        self.depth_now = self._depth_of(self.block_now)
        self.turns_by_depth[self.depth_now] += 1
        return self.depth_now

    def snapshot(self) -> Dict[str, Any]:
        by_depth = dict(self.turns_by_depth)
        turns = sum(by_depth.values())
        shallow = turns - by_depth[self.cap]
        host, step = self.host_turn_s, self.device_step_s
        return {
            "depth_now": self.depth_now,
            "depth_cap": self.cap,
            "block_now": self.block_now,
            "host_turn_ms": None if host is None else round(host * 1e3, 3),
            "device_step_ms": None if step is None else round(step * 1e3, 3),
            # ... of the block the depth was worked out for
            "device_entry_ms": (None if step is None
                                else round(step * self.block_now * 1e3, 3)),
            "turns_by_depth": by_depth,
            # decode dispatch decisions taken under a depth below the cap
            "shallow_share": round(shallow / turns, 4) if turns else 0.0,
            # decode blocks dispatched, by why they ran the steps they did
            "blocks": dict(self.blocks),
            "resets_by_dry_sync": self.resets_by_dry_sync,
        }
