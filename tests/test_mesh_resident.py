"""Mesh-sharded resident snapshot (ISSUE 7): one cluster over N chips.

The contract under test, in three parts:

* **bit parity** — a servicer whose resident snapshot is sharded over a
  cluster mesh must answer Sync/Score/Assign byte-identically to the
  single-chip oracle, with LoadAware on and off and across mesh sizes
  (the ISSUE 7 acceptance fuzz over mesh ∈ {1, 2, 8} forced-host
  devices);
* **shard-local warm path** — a delta Sync lands as a shard-local
  scatter (solver/resident.py ``_scatter_flat_sharded``) and the
  resulting resident tensors are bit-equal to a COLD full upload of the
  same logical state, with every leaf still carrying its
  ``NamedSharding`` (node tensors split along the cluster axis, pod and
  quota rows replicated);
* **placement** — ``parallel.mesh.snapshot_shardings`` attaches a spec
  to every snapshot leaf and ``shard_cluster_snapshot`` rejects node
  buckets that do not divide over the mesh.

The zero-retrace guarantee of the warm sharded stream lives in
tests/test_resident_warm.py next to its single-chip siblings.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.bridge.state import ResidentState, numpy_to_tensor
from koordinator_tpu.config import CycleConfig, MOST_ALLOCATED
from koordinator_tpu.parallel import (
    cluster_mesh,
    shard_cluster_snapshot,
    snapshot_shardings,
)

from test_resident_warm import _full_sync_request, _mutate, _random_state

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def _assign_fields(reply):
    return (tuple(reply.assignment), tuple(reply.status), reply.path)


def _score_fields(reply):
    return (
        reply.flat.pod_index, reply.flat.counts,
        reply.flat.node_index, reply.flat.score,
    )


class TestMeshParityFuzz:
    @pytest.mark.parametrize("mesh_size", [1, 2, 8])
    @pytest.mark.parametrize("loadaware", [True, False])
    def test_mesh_cycles_bit_identical_to_single_chip(self, mesh_size,
                                                      loadaware):
        """The ISSUE 7 acceptance fuzz: drive the SAME wire frames (one
        full Sync, then randomized warm mutations — sparse deltas, full
        tensors, scalar-column churn, resizes) through a single-chip
        oracle and a mesh-resident servicer, asserting every Assign and
        Score reply identical at each step."""
        rng = np.random.RandomState(200 + 8 * mesh_size + loadaware)
        state = _random_state(rng, n_nodes=9, n_pods=24, with_quota=True)
        # the shard cycle has no Strict gang rollback: a snapshot with
        # gangs is served off it (run_cycle), so this fuzz is gang-free
        state["pod_gang"][:] = -1
        cfg = CycleConfig(enable_loadaware=loadaware)
        oracle = ScorerServicer(cfg)
        meshed = ScorerServicer(
            cfg,
            mesh=cluster_mesh(jax.devices()[:mesh_size]),
            mesh_resident=True,
        )
        req = _full_sync_request(state)
        oracle.sync(req)
        meshed.sync(req)
        for step in range(6):
            a = oracle.assign(
                pb2.AssignRequest(snapshot_id=oracle.snapshot_id())
            )
            b = meshed.assign(
                pb2.AssignRequest(snapshot_id=meshed.snapshot_id())
            )
            # identical placements/statuses; the paths legitimately
            # differ (shard vs scan) — that is the point
            assert a.assignment == b.assignment, (mesh_size, loadaware, step)
            assert a.status == b.status, (mesh_size, loadaware, step)
            assert b.path == "shard"
            sa = oracle.score(pb2.ScoreRequest(
                snapshot_id=oracle.snapshot_id(), top_k=3, flat=True
            ))
            sb = meshed.score(pb2.ScoreRequest(
                snapshot_id=meshed.snapshot_id(), top_k=3, flat=True
            ))
            assert _score_fields(sa) == _score_fields(sb), (
                mesh_size, loadaware, step
            )
            mreq, _ = _mutate(rng, state)
            oracle.sync(mreq)
            meshed.sync(mreq)
            assert oracle.state.last_sync_path == meshed.state.last_sync_path

    def test_most_allocated_strategy_parity(self):
        """The closed-universe certificate path (MostAllocated) must
        hold the same parity on the mesh-resident snapshot."""
        rng = np.random.RandomState(77)
        state = _random_state(rng, n_nodes=8, n_pods=20, with_quota=False)
        cfg = CycleConfig(fit_scoring_strategy=MOST_ALLOCATED)
        oracle = ScorerServicer(cfg)
        meshed = ScorerServicer(
            cfg, mesh=cluster_mesh(jax.devices()), mesh_resident=True
        )
        req = _full_sync_request(state)
        oracle.sync(req)
        meshed.sync(req)
        a = oracle.assign(pb2.AssignRequest(snapshot_id=oracle.snapshot_id()))
        b = meshed.assign(pb2.AssignRequest(snapshot_id=meshed.snapshot_id()))
        assert a.assignment == b.assignment and a.status == b.status


class TestShardLocalDelta:
    def _delta_step(self, sv, state, rng):
        """One warm node-tensor delta shipped to ``sv``; mutates
        ``state`` in place."""
        choices = [("node_usage", "usage"), ("node_requested", "requested")]
        key, attr = choices[rng.randint(len(choices))]
        prev = state[key].copy()
        state[key][
            rng.randint(0, state[key].shape[0]), rng.randint(0, 13)
        ] += int(rng.randint(1, 100))
        req = pb2.SyncRequest()
        getattr(req.nodes, attr).CopyFrom(numpy_to_tensor(state[key], prev))
        assert getattr(req.nodes, attr).delta_idx  # sparse on the wire
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"

    def test_warm_deltas_bit_equal_cold_full_upload(self):
        """After a run of shard-local delta scatters, every resident
        leaf must be bit-equal to a COLD mesh-resident rebuild of the
        same logical state (and to the single-chip resident state) —
        the warm sharded path edits exactly the padded cells the cold
        sharded encode would write."""
        mesh = cluster_mesh(jax.devices())
        rng = np.random.RandomState(55)
        state = _random_state(rng, n_nodes=7, n_pods=16, with_quota=True)
        warm = ScorerServicer(mesh=mesh, mesh_resident=True)
        warm.sync(_full_sync_request(state))
        warm.state.snapshot()
        for _ in range(8):
            self._delta_step(warm, state, rng)
        cold = ScorerServicer(mesh=mesh, mesh_resident=True)
        cold.sync(_full_sync_request(state))
        single = ScorerServicer()
        single.sync(_full_sync_request(state))

        got = jax.tree_util.tree_leaves(warm.state.snapshot())
        want = jax.tree_util.tree_leaves(cold.state.snapshot())
        oracle = jax.tree_util.tree_leaves(single.state.snapshot())
        assert len(got) == len(want) == len(oracle)
        for g, w, o in zip(got, want, oracle):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(o))

    def test_warm_delta_preserves_shardings(self):
        """The scatter's in/out specs match, so a warm update must hand
        back tensors with the SAME NamedSharding — a silent regather
        would turn every later launch into a resharding copy."""
        mesh = cluster_mesh(jax.devices())
        rng = np.random.RandomState(63)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=False)
        sv = ScorerServicer(mesh=mesh, mesh_resident=True)
        sv.sync(_full_sync_request(state))
        before = sv.state.snapshot()
        self._delta_step(sv, state, rng)
        after = sv.state.snapshot()
        assert after is not before  # warm update rebuilt the pytree
        assert after.nodes.usage.sharding.spec == P("nodes", None)
        assert len(after.nodes.usage.sharding.device_set) == mesh.size
        assert after.pods.requests.sharding.spec == P()

    def test_indivisible_bucket_falls_back_single_chip(self):
        """A node bucket that does not divide over the mesh must not
        crash — the snapshot stays single-chip for that geometry (and
        the servicer still answers correctly)."""
        mesh = cluster_mesh(jax.devices()[:3])  # 3 never divides 8/16/...
        rng = np.random.RandomState(71)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=False)
        sv = ScorerServicer(mesh=mesh, mesh_resident=True)
        sv.sync(_full_sync_request(state))
        assert sv.state.active_mesh() is None
        snap = sv.state.snapshot()
        oracle = ScorerServicer()
        oracle.sync(_full_sync_request(state))
        a = oracle.assign(pb2.AssignRequest(snapshot_id=oracle.snapshot_id()))
        b = sv.assign(pb2.AssignRequest(snapshot_id=sv.snapshot_id()))
        assert a.assignment == b.assignment
        del snap


class TestShardingSpecs:
    def test_snapshot_shardings_cover_every_leaf(self):
        from koordinator_tpu.harness import generators
        from koordinator_tpu.model import encode_snapshot

        n, p, g, q = generators.loadaware_joint(seed=5, pods=32, nodes=16)
        snap = encode_snapshot(n, p, g, q)
        mesh = cluster_mesh(jax.devices())
        specs = snapshot_shardings(snap, mesh)
        snap_leaves, snap_def = jax.tree_util.tree_flatten(snap)
        spec_leaves, spec_def = jax.tree_util.tree_flatten(specs)
        assert len(snap_leaves) == len(spec_leaves)
        sharded = shard_cluster_snapshot(snap, mesh)
        assert sharded.nodes.allocatable.sharding.spec == P("nodes", None)
        assert sharded.nodes.metric_fresh.sharding.spec == P("nodes")
        assert sharded.nodes.agg_usage.sharding.spec == P(
            "nodes", None, None
        )
        assert sharded.pods.requests.sharding.spec == P()
        assert sharded.quotas.runtime.sharding.spec == P()
        np.testing.assert_array_equal(
            np.asarray(sharded.nodes.allocatable),
            np.asarray(snap.nodes.allocatable),
        )

    def test_resident_placement_matches_snapshot_shardings(self):
        """The lockstep guard: ResidentState's incremental per-field
        placement and parallel.mesh.snapshot_shardings are two
        statements of ONE policy — every leaf of a mesh-resident
        snapshot must carry exactly the NamedSharding the canonical
        spec tree prescribes.  A future snapshot field classified
        differently in the two places fails here instead of silently
        mis-sharding the live snapshot."""
        mesh = cluster_mesh(jax.devices())
        rng = np.random.RandomState(91)
        state = _random_state(rng, n_nodes=8, n_pods=16, with_quota=True)
        sv = ScorerServicer(mesh=mesh, mesh_resident=True)
        sv.sync(_full_sync_request(state))
        snap = sv.state.snapshot()
        specs = snapshot_shardings(snap, mesh)
        snap_leaves = jax.tree_util.tree_leaves(snap)
        spec_leaves = jax.tree_util.tree_leaves(specs)
        assert len(snap_leaves) == len(spec_leaves)
        for leaf, spec in zip(snap_leaves, spec_leaves):
            assert leaf.sharding == spec, (leaf.shape, leaf.sharding, spec)

    def test_indivisible_bucket_rejected(self):
        from koordinator_tpu.harness import generators
        from koordinator_tpu.model import encode_snapshot

        n, p, g, q = generators.loadaware_joint(seed=5, pods=32, nodes=16)
        snap = encode_snapshot(n, p, g, q)
        mesh = cluster_mesh(jax.devices()[:3])
        with pytest.raises(ValueError, match="does not divide"):
            shard_cluster_snapshot(snap, mesh)


class TestPow2DeviceCount:
    def test_rounds_down_to_power_of_two(self):
        from koordinator_tpu.parallel import pow2_device_count

        assert [pow2_device_count(n) for n in (1, 2, 3, 5, 6, 8, 9, 15)] \
            == [1, 2, 2, 4, 4, 8, 8, 8]
        assert pow2_device_count(0) == 1  # clamped, never zero

    def test_daemon_mesh_flag_normalizes(self):
        """The daemon rounds --mesh down to a power-of-two prefix (a
        6-device cluster mesh would never divide a power-of-two node
        bucket — the snapshot would silently stay single-chip, the
        exact capacity the flag exists to exceed) and rejects garbage
        cleanly."""
        import os
        import tempfile

        from koordinator_tpu.scheduler.server import SchedulerServer

        tmp = tempfile.mkdtemp()

        def build(spec):
            s = SchedulerServer(
                lease_path=os.path.join(tmp, "leader.lease"),
                uds_path=os.path.join(tmp, f"scorer-{spec}.sock"),
                http_port=0,
                enable_grpc=False,
                state_dir=None,
                mesh_devices=spec,
            )
            try:
                return s.servicer.mesh.size
            finally:
                s._httpd.server_close()

        assert build("6") == 4
        assert build("auto") == 8
        with pytest.raises(ValueError, match="device count or 'auto'"):
            build("banana")


class TestMeshResidentState:
    def test_state_without_mesh_unchanged(self):
        """The default (mesh=None) ResidentState is byte-for-byte the
        pre-ISSUE-7 behavior — plain single-device arrays."""
        rng = np.random.RandomState(81)
        state = _random_state(rng, n_nodes=5, n_pods=10, with_quota=False)
        sv = ScorerServicer()
        sv.sync(_full_sync_request(state))
        assert isinstance(sv.state, ResidentState)
        assert sv.state.mesh is None and sv.state.active_mesh() is None
