"""A whole run at a test's size, past the harness's look for a chip: a
sound run is correct; with the timed path broken underneath it is not.

Faults, each planted in the servicer under the client (the path the
window drives): a Sync that leaves the state unchanged, an Assign that
leaves half of the backlog out, an answer altered where it is produced.
The cells run on one chip, so there is no exchange between chips to
leave out.  The control (the reference on the state before each Sync,
in the program's place) must read not correct through the same
comparison."""

import numpy as np
import pytest

from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer

CELLS = ["colo2k.batch", "k8s5k.batch", "k8s5k.prescore"]


def _run(cell, seed=2**31 + 3, control=False):
    from benchmark.run import run_cell

    return run_cell(cell, seed, 1.0, False, control=control,
                    out=lambda *a, **k: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny_cell, name):
    res = _run(tiny_cell(name))
    assert res["correct"], res
    assert res["attempted"] > 5 and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny_cell, name):
    res = _run(tiny_cell(name), control=True)
    assert not res["correct"], res["checks"]
    assert all(c["value"] > c["limit"] for c in res["checks"].values())


def _state_unchanged(monkeypatch):
    sync = ScorerServicer.sync
    calls = []

    def stale(self, req, ctx=None, wire_bytes=None):
        calls.append(1)
        if len(calls) > 1:  # acknowledge every later Sync, apply nothing
            req, wire_bytes = pb2.SyncRequest(), None
        return sync(self, req, ctx, wire_bytes)

    monkeypatch.setattr(ScorerServicer, "sync", stale)


def _half_left_out(monkeypatch):
    assign = ScorerServicer.assign

    def half(self, req, ctx=None):
        reply = assign(self, req, ctx)
        a = list(reply.assignment)
        a[len(a) // 2:] = [-1] * (len(a) - len(a) // 2)
        del reply.assignment[:]
        reply.assignment.extend(a)
        return reply

    monkeypatch.setattr(ScorerServicer, "assign", half)


def _answer_altered(monkeypatch):
    assign, score = ScorerServicer.assign, ScorerServicer.score

    def altered_assign(self, req, ctx=None):
        reply = assign(self, req, ctx)
        placed = [i for i, v in enumerate(reply.assignment) if v >= 0]
        if len(placed) > 1:
            a, b = placed[0], placed[-1]
            va, vb = reply.assignment[a], reply.assignment[b]
            if va == vb:
                vb = (vb + 1) % 24
            reply.assignment[a], reply.assignment[b] = vb, va
        return reply

    def altered_score(self, req, ctx=None):
        reply = score(self, req, ctx)
        s = np.frombuffer(reply.flat.score, "<i8").copy()
        if len(s):
            s[-1] += 1
            reply.flat.score = s.tobytes()
        return reply

    monkeypatch.setattr(ScorerServicer, "assign", altered_assign)
    monkeypatch.setattr(ScorerServicer, "score", altered_score)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


# a one-pod table has no half to leave out
CASES = [(n, f) for n in CELLS for f in sorted(FAULTS)
         if not (f == "half_left_out" and n.endswith("prescore"))]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(tiny_cell, monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    res = _run(tiny_cell(name))
    assert not res["correct"], res["checks"]
