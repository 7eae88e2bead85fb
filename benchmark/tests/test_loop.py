"""The warm-up's reach: which device updates a Sync's changed cells run."""

import pytest

from benchmark.harness.loop import buckets_between, scatter_bucket


@pytest.mark.parametrize("changed,size,want", [
    (0, 1000, None), (1, 1000, 8), (8, 1000, 8), (9, 1000, 16),
    (250, 1000, 256), (251, 1000, "full"), (3, 13, 8), (4, 13, "full")])
def test_scatter_bucket(changed, size, want):
    assert scatter_bucket(changed, size) == want


def test_buckets_between_spans_every_power_of_two():
    assert buckets_between(3000, 9000, 10**6) == {4096, 8192, 16384}
    assert buckets_between(5, 7, 10**6) == {8}
    assert buckets_between(200, 300, 1000) == {256, "full"}


# -- the loop driven by the reference in the program's place --------------

import copy  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

from benchmark.harness import cluster as C  # noqa: E402
from benchmark.harness import reference  # noqa: E402
from benchmark.harness.loop import ASSIGNED, Loop, copy_state  # noqa: E402

WAIT_GANG = 2


def _kw_bytes(kw):
    out = []
    for k in sorted(kw):
        v = kw[k]
        if isinstance(v, np.ndarray):
            out.append(f"{k}:{v.dtype.str}:{v.shape}:".encode() + v.tobytes())
        else:
            out.append(f"{k}:".encode() + json.dumps(v).encode())
    return b"|".join(out)


class RefClient:
    """Stands in for the served path: keeps every Sync's keywords, and
    answers each Assign with the reference on the state that Sync
    carried (ASSIGNED where it places a pod)."""

    def __init__(self, scoring):
        self.scoring = scoring
        self.state = None
        self.sent = []
        self.syncs = hashlib.blake2b(digest_size=16)
        self.answers = hashlib.blake2b(digest_size=16)

    def keep(self, state):
        self.state = copy_state(state)

    def sync(self, **kw):
        self.sent.append(kw)
        self.syncs.update(_kw_bytes(kw))

    def assign(self):
        a = reference.assign(self.state, self.scoring)
        self.answers.update(np.ascontiguousarray(a, "<i8").tobytes())
        return a, np.where(a >= 0, ASSIGNED, 1), 0.0, "reference"


# Taken on the commit before gangs came into the harness (535c01f): the
# Sync keywords and the reference's answers of 20 cycles at seed 7.  A
# gang-free cell must read exactly as it did.  To take them again, run
# this test from a ``git archive`` of that commit, with ``ASSIGNED = 0``
# in place of its import from ``loop``, which that commit lacks.
DIGESTS = {
    "colo2k.batch": ("7813d4c9b37c6239cb13ab73a648a847",
                     "93d23892e6d82fb09042e9fed7b281e8"),
    "k8s5k.batch": ("991538454c02b172f49c71dd69f24a87",
                    "b0ae0effc9db0e321a0d7d16239c4b64"),
}


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_gang_free_cells_read_as_before(tiny_cell, name):
    cell = tiny_cell(name)
    loop = Loop(cell.config, cell.traffic, 7)
    client = RefClient(cell.config["scoring"])
    for _ in range(20):
        loop.cycle(client, keep=client.keep)
    assert (client.syncs.hexdigest(), client.answers.hexdigest()) == \
        DIGESTS[name]
    assert all(kw["gang_id"] == [-1] * 96 for kw in client.sent)
    assert not any("gang_min_member" in kw for kw in client.sent)


def _gang_cell(tiny_cell, members=8, backlog=400):
    """colo2k with a gangs block, at a size where gangs fail."""
    cell = tiny_cell("colo2k.batch", backlog=backlog)
    cell.config = copy.deepcopy(cell.config)
    cell.config["gangs"] = {"members": members, "min_member": members,
                            "mode": "Strict"}
    return cell


def _aligned(loop, m):
    p = loop.pods
    for col in (p.requests, p.limits, p.priority, p.pclass, p.quota,
                loop.pod_est):
        blocks = np.asarray(col).reshape(len(p) // m, m, -1)
        assert (blocks == blocks[:, :1]).all()
    assert loop.gang_id.tolist() == [i // m for i in range(len(p))]


def test_gang_rows_stay_aligned_one_draw_per_gang(tiny_cell, monkeypatch):
    cell = _gang_cell(tiny_cell)
    draws = []
    draw_pods = C.draw_pods

    def counted(gen, spec, count, prefix, first, members=1):
        draws.append((count, members))
        return draw_pods(gen, spec, count, prefix, first, members)

    monkeypatch.setattr(C, "draw_pods", counted)
    loop = Loop(cell.config, cell.traffic, 2**31 + 7)
    client = RefClient(cell.config["scoring"])
    _aligned(loop, 8)
    bound, partway = [], 0
    for _ in range(20):
        loop.cycle(client, keep=client.keep)
        _aligned(loop, 8)
        partway += reference.run(client.state, client.scoring)[1]
        bound.append(int((loop.answer >= 0).sum()))
    # each cycle's bound gangs are refilled before the next, whole, with
    # one draw of the distribution per gang
    assert draws and all(m == 8 and c % 8 == 0 for c, m in draws)
    assert [c for c, _ in draws] == [b for b in bound[:-1] if b]
    assert all(b % 8 == 0 for b in bound)
    assert partway > 0  # the size makes gangs fail partway


class PartialClient:
    """Places every pod, but leaves one member of each odd gang waiting
    (WAIT_GANG): a program whose gang post-pass let a short gang half
    through."""

    def __init__(self, m, rows):
        self.m, self.rows = m, rows

    def sync(self, **kw):
        pass

    def assign(self):
        n = self.rows
        a = np.arange(n) % 24
        status = np.full(n, ASSIGNED)
        status[self.m + 3::2 * self.m] = WAIT_GANG
        return a, status, 0.0, "fake"


def test_binder_binds_whole_gangs_only(tiny_cell):
    cell = _gang_cell(tiny_cell, backlog=96)
    cell.traffic["departures"] = "none"  # keep what was bound in view
    loop = Loop(cell.config, cell.traffic, 5)
    client = PartialClient(8, 96)
    loop.cycle(client)
    names = list(loop.pods.names)
    loop.advance()
    # gangs 0, 2, 4, ... bind and are replaced; the others wait whole
    assert len(loop.bound_node) == 6 * 8
    assert loop.bound_node.tolist() == [
        r % 24 for g in range(0, 12, 2) for r in range(8 * g, 8 * g + 8)]
    for g in range(12):
        rows = range(8 * g, 8 * g + 8)
        moved = [loop.pods.names[r] != names[r] for r in rows]
        assert all(moved) if g % 2 == 0 else not any(moved)
    assert (loop.answer[8 + 3::16] == -1).all()


def test_gang_table_rides_the_first_sync_only(tiny_cell):
    cell = _gang_cell(tiny_cell, backlog=96)
    loop = Loop(cell.config, cell.traffic, 3)
    client = RefClient(cell.config["scoring"])
    for _ in range(4):
        loop.cycle(client, keep=client.keep)
    assert client.sent[0]["gang_min_member"] == [8] * 12
    assert not any("gang_min_member" in kw for kw in client.sent[1:])
    assert all(kw["gang_id"] == [i // 8 for i in range(96)]
               for kw in client.sent)


@pytest.mark.parametrize("gangs,says", [
    ({"members": 8, "min_member": 6, "mode": "Strict"}, "min_member"),
    ({"members": 8, "min_member": 8, "mode": "NonStrict"}, "Strict"),
    ({"members": 7, "min_member": 7, "mode": "Strict"}, "multiple"),
])
def test_unsupported_gangs_fail_at_load(tiny_cell, gangs, says):
    cell = tiny_cell("colo2k.batch", backlog=96)
    with pytest.raises(ValueError, match=says):
        C.validate_gangs(dict(cell.config, gangs=gangs), cell.traffic)
