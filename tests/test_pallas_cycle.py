"""Parity: the dense Pallas cycle kernel must be bit-identical with the
lax.scan reference path (solver/greedy.py) — same placements, same
post-cycle state — across strategies, gangs, quotas and padding shapes,
both as the plain kernel and as its Strict-gang variant run on the same
inputs.  The multi-chip round-based cycle (``greedy_assign_waves``) meets
the same scenarios on the test mesh.

Runs in Pallas interpret mode on the CPU test platform; the compiled TPU
path is exercised by chip_smoke.py and the benchmark on hardware.
"""

from functools import partial

import jax
import numpy as np
import pytest

from koordinator_tpu.config import CycleConfig
from koordinator_tpu.harness import generators
from koordinator_tpu.model import encode_snapshot
from koordinator_tpu.parallel import greedy_assign_waves, make_mesh
from koordinator_tpu.solver import (
    STATUS_ASSIGNED,
    STATUS_WAIT_GANG,
    greedy_assign,
)
from koordinator_tpu.solver.pallas_dense import greedy_assign_dense as _dense

# the kernel's Strict-gang variant, forced on: on a gang-free snapshot
# every pod takes its gang-free gate, and placements must not move
_dense_gang = partial(_dense, has_gangs=True)


@pytest.fixture(params=["dense", "dense_gang"])
def dense_kernel(request):
    """Both kernel variants must hold the same bit-parity contract."""
    return _dense if request.param == "dense" else _dense_gang


def _quota_snapshot(pods=48, nodes=16, **buckets):
    return generators.quota_colocation_snapshot(
        pods=pods, nodes=nodes, **buckets
    )[0]


def _assert_equal(scan, pallas):
    np.testing.assert_array_equal(
        np.asarray(scan.assignment), np.asarray(pallas.assignment)
    )
    np.testing.assert_array_equal(np.asarray(scan.status), np.asarray(pallas.status))
    np.testing.assert_array_equal(
        np.asarray(scan.node_requested), np.asarray(pallas.node_requested)
    )
    np.testing.assert_array_equal(
        np.asarray(scan.node_estimated), np.asarray(pallas.node_estimated)
    )
    np.testing.assert_array_equal(
        np.asarray(scan.quota_used), np.asarray(pallas.quota_used)
    )


class TestPallasCycleParity:
    def test_quota_colocation_default_cfg(self, dense_kernel):
        snap = _quota_snapshot()
        _assert_equal(greedy_assign(snap), dense_kernel(snap, interpret=True))

    def test_most_allocated_strategy(self, dense_kernel):
        snap = _quota_snapshot(pods=32, nodes=8)
        cfg = CycleConfig(fit_scoring_strategy="MostAllocated")
        _assert_equal(
            greedy_assign(snap, cfg), dense_kernel(snap, cfg, interpret=True)
        )

    def test_loadaware_disabled(self, dense_kernel):
        snap = _quota_snapshot(pods=32, nodes=8)
        cfg = CycleConfig(enable_loadaware=False)
        _assert_equal(
            greedy_assign(snap, cfg), dense_kernel(snap, cfg, interpret=True)
        )

    def test_gangs_and_overload(self, dense_kernel):
        nodes_l, pods_l, gangs = generators.loadaware_joint(seed=3, pods=40, nodes=6)[:3]
        snap = encode_snapshot(nodes_l, pods_l, gangs, [])
        _assert_equal(greedy_assign(snap), dense_kernel(snap, interpret=True))

    def test_unpadded_bucket_shapes(self, dense_kernel):
        # bucket sizes not multiples of 8/128 must still agree
        snap = _quota_snapshot(pods=21, nodes=5, node_bucket=5, pod_bucket=21)
        _assert_equal(greedy_assign(snap), dense_kernel(snap, interpret=True))

    def test_scarce_capacity_leaves_unscheduled(self, dense_kernel):
        nodes_l, pods_l, gangs = generators.loadaware_joint(seed=7, pods=64, nodes=2)[:3]
        snap = encode_snapshot(nodes_l, pods_l, gangs, [])
        scan = greedy_assign(snap)
        pallas = dense_kernel(snap, interpret=True)
        _assert_equal(scan, pallas)
        assert int((np.asarray(scan.assignment) < 0).sum()) > 0

    def test_extended_plugin_tensors(self, dense_kernel):
        """extra_mask/extra_scores ride the kernel as [N, P] tiles and stay
        bit-identical with the scan path carrying the same tensors."""
        import jax.numpy as jnp

        snap = _quota_snapshot(pods=40, nodes=12)
        P = snap.pods.capacity
        N = snap.nodes.allocatable.shape[0]
        rng = np.random.default_rng(11)
        extra_mask = jnp.asarray(rng.random((P, N)) > 0.25)
        extra_scores = jnp.asarray(rng.integers(0, 60, size=(P, N)), dtype=jnp.int64)
        want = greedy_assign(snap, extra_mask=extra_mask, extra_scores=extra_scores)
        got = dense_kernel(
            snap, interpret=True, extra_mask=extra_mask, extra_scores=extra_scores
        )
        _assert_equal(want, got)

    def test_extended_mask_only(self, dense_kernel):
        import jax.numpy as jnp

        snap = _quota_snapshot(pods=24, nodes=8)
        P = snap.pods.capacity
        N = snap.nodes.allocatable.shape[0]
        rng = np.random.default_rng(5)
        extra_mask = jnp.asarray(rng.random((P, N)) > 0.5)
        want = greedy_assign(snap, extra_mask=extra_mask)
        got = dense_kernel(snap, interpret=True, extra_mask=extra_mask)
        _assert_equal(want, got)


class TestPallasWaveParity:
    """The round-based cycle (``greedy_assign_waves`` on the 8-device
    test mesh): frozen top-M candidates, certified prefix commits, live
    in-wave Reserve.  Placements must stay bit-identical with the scan
    across widths, strategies, quotas and extras, and the round count
    must show the batching.  A gang snapshot never reaches that path
    (solver.run_cycle, bridge/server.py), so the gang scenario runs the
    dense kernel's Strict-gang variant."""

    def test_wave_knobs_parity_and_rounds(self):
        snap = _quota_snapshot(pods=48, nodes=16)
        want = greedy_assign(snap)
        for wave, top_m in ((8, 2), (32, 4)):
            got, rounds = greedy_assign_waves(
                snap, make_mesh(), wave=wave, top_m=top_m
            )
            _assert_equal(want, got)
            assert 1 <= rounds < snap.pods.capacity

    def test_wave_most_allocated(self):
        """MostAllocated rides the closed candidate universe."""
        snap = _quota_snapshot(pods=32, nodes=8)
        cfg = CycleConfig(fit_scoring_strategy="MostAllocated")
        got, _ = greedy_assign_waves(snap, make_mesh(), cfg, wave=8, top_m=4)
        _assert_equal(greedy_assign(snap, cfg), got)

    def test_wave_extended_plugin_tensors(self):
        import jax.numpy as jnp

        snap = _quota_snapshot(pods=40, nodes=12)
        P = snap.pods.capacity
        N = snap.nodes.allocatable.shape[0]
        rng = np.random.default_rng(17)
        extra_mask = jnp.asarray(rng.random((P, N)) > 0.25)
        extra_scores = jnp.asarray(
            rng.integers(0, 60, size=(P, N)), dtype=jnp.int64
        )
        want = greedy_assign(
            snap, extra_mask=extra_mask, extra_scores=extra_scores
        )
        got, _ = greedy_assign_waves(
            snap, make_mesh(), extra_mask=extra_mask,
            extra_scores=extra_scores, wave=8, top_m=2,
        )
        _assert_equal(want, got)

    def test_wave_contention_degrades_to_single_commits(self):
        """Identical pods racing for one-pod-each nodes with a single
        candidate each on a 4-chip mesh: candidates fill in-wave,
        uncertifiable pods end the prefix, and every pod must place
        exactly like the scan."""
        nodes_l = [
            {
                "name": f"tight-{i}",
                "allocatable": {"cpu": "1000m", "memory": 1 << 30,
                                "pods": 110},
            }
            for i in range(16)
        ]
        pods_l = [
            {
                "name": f"pod-{p}",
                "requests": {"cpu": "900m", "memory": 512 << 20, "pods": 1},
            }
            for p in range(12)
        ]
        snap = encode_snapshot(nodes_l, pods_l, [], [])
        want = greedy_assign(snap)
        got, rounds = greedy_assign_waves(
            snap, make_mesh(jax.devices()[:4]), wave=8, top_m=1
        )
        _assert_equal(want, got)
        assert int((np.asarray(got.assignment) >= 0).sum()) == 12
        # every pod's single candidate is the node the pod before it
        # took: each round certifies one pod
        assert rounds == 12

    def test_wave_gangs(self):
        """Strict gangs under MostAllocated through the kernel's gang
        variant: some gangs fall short of min member and are undone."""
        nodes_l, pods_l, gangs, quotas = generators.gang_batch(
            seed=5, pods=40, nodes=1, min_member=6
        )
        snap = encode_snapshot(nodes_l, pods_l, gangs, quotas)
        cfg = CycleConfig(fit_scoring_strategy="MostAllocated")
        got = _dense_gang(snap, cfg, interpret=True)
        _assert_equal(greedy_assign(snap, cfg), got)
        status = np.asarray(got.status)[: len(pods_l)]
        assert (status == STATUS_WAIT_GANG).any(), "no gang undone"
        assert (status == STATUS_ASSIGNED).any(), "no gang placed"
