"""Deciding ``correct``: the timed path's answers against the reference.

After the window has closed, the answers the served path gave inside it
are compared with ``reference.py`` on the very state each Sync carried:

* Assign traffic: a sample of the window's cycles, drawn from the seed
  by reservoir sampling (every cycle equally likely, whatever their
  number); every pod row of a sampled cycle is compared.  Both sides
  give bound answers: the program's is the node where its status is
  ASSIGNED, else -1 (``Loop.cycle``); the reference's places Strict
  gangs all or nothing.  Where no pod has a gang, every engine sets
  ASSIGNED exactly when it places the pod, so this is the comparison of
  placements.  The number compared is the count of pods whose bound
  answers differ (limit 0).
* ScoreFlat traffic: every cycle of the window.  The reference replays
  the window from the state of its first Sync, binding each pod where
  its own list puts it first, and the number compared is the count of
  cycles whose list (nodes, their order and scores) differs (limit 0).

The control (``control=True``) puts the reference in the program's
place, computed on the state before the cycle's Sync: the acknowledged
Sync left unseen, which breaks the stated guarantee that every
acknowledged Sync is visible to the next request.  Its answers go
through the same comparison, so the run must come out not correct.
(A window's first cycle has no state before it; there the program's
own answer stands.)
"""

from __future__ import annotations

import numpy as np

from . import reference
from .loop import copy_state, digest


class AssignCheck:
    def __init__(self, gen, sample: int, control: bool = False):
        self.gen = gen
        self.k = int(sample)
        self.control = control
        self.slots = []  # [state, previous state or None, answer]
        self.seen = 0
        self.current = None
        self.prev = None

    def keep(self, loop, state):
        """Called with each window cycle's state, before its Sync."""
        i = self.seen
        self.seen += 1
        slot = None
        if i < self.k:
            slot = len(self.slots)
            self.slots.append(None)
        else:
            j = int(self.gen.integers(0, i + 1))
            if j < self.k:
                slot = j
        snap = copy_state(state) if (slot is not None or self.control) \
            else None
        if slot is not None:
            self.slots[slot] = [snap, self.prev, None]
        self.current = slot
        if self.control:
            self.prev = snap

    def answer(self, loop, rec):
        if self.current is not None and loop.answer is not None:
            self.slots[self.current][2] = np.array(loop.answer, copy=True)

    def finish(self, scoring):
        mismatch = checked = 0
        for state, prev, got in self.slots:
            if got is None:
                continue
            want = reference.assign(state, scoring)
            if self.control and prev is not None:
                stale = dict(state, **{k: prev[k] for k in (
                    "node_requested", "node_usage", "pod_requests",
                    "pod_estimated", "priority", "gang_id", "quota_id",
                    "quota_runtime", "quota_used", "quota_limited")
                    if k in prev})
                got = reference.assign(stale, scoring)
            mismatch += int((want != got).sum())
            checked += len(want)
        return {
            "numbers": {"placement_mismatch": mismatch},
            "info": {"checked_cycles": sum(s[2] is not None
                                           for s in self.slots),
                     "checked_pods": checked},
        }


class ScoreCheck:
    _POD_KEYS = ("pod_requests", "pod_estimated", "priority", "quota_id")

    def __init__(self, gen, sample, control: bool = False):
        self.control = control
        self.start = None
        self.steps = []  # per cycle: usage rows, their usage, pod rows
        self.digests = []

    def keep(self, loop, state):
        if self.start is None:
            self.start = copy_state(state)
            rows = np.zeros(0, np.int64)
        else:
            rows = np.array(loop.last_usage_rows, copy=True)
        self.steps.append((rows, state["node_usage"][rows].copy(),
                           {k: np.array(state[k], copy=True)
                            for k in self._POD_KEYS}))

    def answer(self, loop, rec):
        self.digests.append(rec["digest"])

    def finish(self, scoring):
        state = copy_state(self.start) if self.start is not None else None
        mismatch = 0
        placed_req, placed_node = None, -1
        for i, ((rows, usage, pod), got) in enumerate(
                zip(self.steps, self.digests)):
            before = copy_state(state) if self.control and i else None
            if i and placed_node >= 0:
                state["node_requested"][placed_node] += placed_req
            state["node_usage"][rows] = usage
            state.update(pod)
            ni, sc = reference.score_list(state, scoring)
            if before is not None:
                got = digest(*reference.score_list(dict(before, **pod),
                                                   scoring))
            mismatch += int(digest(ni, sc) != got)
            if len(ni):
                placed_node = int(ni[sc == sc.max()].min())
                placed_req = pod["pod_requests"][0]
            else:
                placed_node = -1
        return {"numbers": {"score_list_mismatch": mismatch},
                "info": {"checked_cycles": len(self.digests)}}


CHECKS = {"assign": AssignCheck, "score_flat": ScoreCheck}

# Exact comparisons: a placement or a score list either equals the
# reference's or it does not.
LIMITS = {"placement_mismatch": 0, "score_list_mismatch": 0}
