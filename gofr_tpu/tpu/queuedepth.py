"""How many decode entries the engine loop keeps in the device's queue.

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
  device entry  the time between the returns of two decode reads in a row
                that both had to wait, the first with a decode entry still
                queued behind it: the device was busy from the one return
                to the other, with one decode entry and the prefills
                queued before it. The LOWER quartile of the recent ones:
                where full and half blocks mix, the shorter is what the
                queue may hold.

The device needs the next entry no later than the end of what is queued:
with d decode entries in flight a read's return leaves it d - 1, so the
host's turn has to fit into d - 1 entries, and

    depth = clip(1 + ceil(k x host_turn / device_entry),
                 min(2, cap), cap)

The rule stands at the cap wherever it cannot know better: without both
estimates (the first turns after a start or a reset), under an admission
plane (a rank's own clock may not choose a program: every rank has to
dispatch the same ones), and after the queue ran dry, which drops both
estimates. No setting, no environment variable and no model's or cell's
name enters it.
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

RING = 32            # recent turns and entries an estimate is taken over
MIN_SAMPLES = 8      # fewer, and there is no estimate
# a read that came back sooner than this found its entry done: the copy
# to the host was started at the enqueue, so it is a memcpy of a block's
# tokens (tens of microseconds, a few milliseconds where the server's
# threads hold the interpreter), where a wait is a good part of a block.
# A read that waited less had a margin this thin: it counts as none
WAITED_S = 2e-3


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
    """The loop's running estimates and the depth they give. Written by
    the loop thread alone; `snapshot` (`/debug/engine` -> `engine.queue`)
    reads plain numbers and a dict whose keys are all there from the
    start, so it takes no lock."""

    def __init__(self, cap: int, mirrored: bool = False):
        self.cap = max(1, int(cap))
        self.mirrored = bool(mirrored)
        self.turns_by_depth: Dict[int, int] = {
            depth: 0 for depth in range(min(2, self.cap), self.cap + 1)}
        self.resets_by_dry_sync = 0
        self.reset()

    def reset(self) -> None:
        """A start or a device-state reset: no estimate, so the cap."""
        self._turns: "collections.deque" = collections.deque(maxlen=RING)
        self._entries: "collections.deque" = collections.deque(maxlen=RING)
        self._turn_s = 0.0          # the open turn's host seconds so far
        self._turn_broken = True    # ... which no decode read's return began
        self._returned_at: Optional[float] = None
        self._found_done = 0        # decode reads in a row that did not wait
        self.host_turn_s: Optional[float] = None
        self.device_entry_s: Optional[float] = None
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

    def note_entry(self, seconds: float) -> None:
        """One decode entry's time on the device."""
        self._entries.append(seconds)

    def note_read(self, returned_at: float, waited_s: float,
                  queued_behind: int) -> None:
        """A decode read's return: `waited_s` it blocked, `queued_behind`
        decode entries the deque still held."""
        waited = waited_s >= WAITED_S
        if waited and self._returned_at is not None:
            self.note_entry(returned_at - self._returned_at)
        self._returned_at = (returned_at if waited and queued_behind
                             else None)
        self._found_done = 0 if waited else self._found_done + 1
        # every entry this depth keeps behind the one being read was done
        # before the host came for it: the device had nothing left
        if self.depth_now < self.cap \
                and self._found_done >= self.depth_now - 1:
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

    # -- the depth ----------------------------------------------------------------
    def turn(self) -> int:
        """Work out this turn's depth, and count the turn under it."""
        self.host_turn_s = _estimate(self._turns, 0.75)
        self.device_entry_s = _estimate(self._entries, 0.25)
        self.depth_now = depth_for(self.host_turn_s, self.device_entry_s,
                                   self.cap, mirrored=self.mirrored)
        self.turns_by_depth[self.depth_now] += 1
        return self.depth_now

    def snapshot(self) -> Dict[str, Any]:
        by_depth = dict(self.turns_by_depth)
        turns = sum(by_depth.values())
        shallow = turns - by_depth[self.cap]
        host, entry = self.host_turn_s, self.device_entry_s
        return {
            "depth_now": self.depth_now,
            "depth_cap": self.cap,
            "host_turn_ms": None if host is None else round(host * 1e3, 3),
            "device_entry_ms": (None if entry is None
                                else round(entry * 1e3, 3)),
            "turns_by_depth": by_depth,
            # decode dispatch decisions taken under a depth below the cap
            "shallow_share": round(shallow / turns, 4) if turns else 0.0,
            "resets_by_dry_sync": self.resets_by_dry_sync,
        }
