"""ISSUE 9: the incremental score engine.

The resident [P, N] score/feasible tensors are first-class leaves of
the device-resident state: warm delta Syncs accumulate dirty
column/row sets through the stage/commit seam (bridge/state.py
``ScoreResidency``) and the next Score advances the tensors by
rescoring ONLY what the deltas invalidated (solver/incremental.py),
bit-identical to a full rescore by construction.

Covered here:

* parity fuzz — randomized warm streams (deltas touching 1..N nodes
  and pods, scalar churn, resizes, full-resync events, interleaved
  Scores at mixed top_k) produce Score replies byte-identical between
  the incremental engine and the full-rescore oracle, on mesh sizes
  {1, 8};
* the ScoreMemo x incremental seam — a memo entry keyed on the
  pre-delta snapshot id never serves after the bump, the incremental
  launch's readback populates the memo for the NEW id, and an
  owner-failure on the incremental launch falls back to a full rescore
  instead of poisoning the resident tensor;
* dirty-set mechanics — accumulation across multiple Syncs between
  Scores, the zero-dirty short-circuit (quota-only deltas: score_cycle
  reads no quota state), the ``--score-incr-max-ratio`` fallback, the
  CycleConfig-change drop, and the ``score_incr=False`` opt-out;
* the replication follower (ISSUE 8 tier) maintaining ITS resident
  score tensors incrementally from streamed delta frames, byte-parity
  with the leader after every applied frame.
"""

import dataclasses

import numpy as np
import pytest

import jax

from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.bridge.state import numpy_to_tensor
from koordinator_tpu.config import CycleConfig
from koordinator_tpu.model.snapshot import PriorityClass
from koordinator_tpu.parallel import cluster_mesh

from test_resident_warm import (
    R,
    _full_sync_request,
    _mutate,
    _random_state,
)


def _flat(sv, k=8):
    """One flat Score's payload bytes (build_ms excluded: it is the
    one timing field and the parity claim is over the data)."""
    reply = sv.score(pb2.ScoreRequest(
        snapshot_id=sv.snapshot_id(), top_k=k, flat=True
    ))
    return reply.flat.SerializeToString()


def _legacy(sv, k=8):
    reply = sv.score(pb2.ScoreRequest(
        snapshot_id=sv.snapshot_id(), top_k=k, flat=False
    ))
    clone = pb2.ScoreReply()
    clone.CopyFrom(reply)
    clone.build_ms = 0.0
    return clone.SerializeToString()


def _incr_count(sv, result):
    return sv.telemetry.registry.get(
        "koord_scorer_score_incr_total", {"result": result}
    ) or 0


def _quota_delta(state, rng):
    """A quota-only sparse delta: dirties ZERO score rows by design
    (score_cycle reads no quota state)."""
    prev = state["quota_used"].copy()
    flat = state["quota_used"].reshape(-1)
    flat[int(rng.randint(flat.size))] += 1
    req = pb2.SyncRequest()
    req.quotas.used.CopyFrom(numpy_to_tensor(state["quota_used"], prev))
    return req


class TestParityFuzz:
    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_matches_full_rescore_oracle(self, seed):
        """The acceptance fuzz: a randomized warm stream through TWO
        servicers — incremental engine vs score_incr=False oracle —
        with Scores at mixed top_k after every frame, byte-identical
        replies throughout, including across resizes (residency drop)
        and a mid-stream full resync."""
        rng = np.random.RandomState(1000 + seed)
        state = _random_state(
            rng, n_nodes=int(rng.randint(4, 10)),
            n_pods=int(rng.randint(8, 24)),
            with_quota=bool(seed % 2),
        )
        # memo stays ON for half the seeds: memo-served batches must be
        # byte-identical too (they slice the incremental launch's
        # readback), and the seam is exactly what ISSUE 9 touches
        memo = bool(seed % 2)
        # ratio gate OPEN (1.0): tiny fuzz geometries put most deltas
        # past the default 0.25 cost gate, and this test exists to
        # exercise the KERNEL maximally — the gate has its own test
        incr = ScorerServicer(score_memo=memo, score_incr_max_ratio=1.0)
        full = ScorerServicer(score_memo=memo, score_incr=False)
        for sv in (incr, full):
            sv.sync(_full_sync_request(state))
        assert _flat(incr, 4) == _flat(full, 4)
        for cycle in range(12):
            if cycle == 6:
                # full-resync event: the whole state rides one cold
                # frame; the resident score tensors must drop, not
                # serve stale columns
                req = _full_sync_request(state)
            else:
                req, _resized = _mutate(rng, state)
            for sv in (incr, full):
                sv.sync(req)
            assert incr.state.last_sync_path == full.state.last_sync_path
            k = int(rng.choice([1, 3, 8, 17]))
            assert _flat(incr, k) == _flat(full, k), (
                f"seed={seed} cycle={cycle} k={k}"
            )
            if rng.rand() < 0.3:
                assert _legacy(incr, 5) == _legacy(full, 5), (
                    f"seed={seed} cycle={cycle} legacy"
                )
        # the stream must actually have exercised the engine
        assert _incr_count(incr, "incr") > 0, "fuzz never ran incrementally"
        assert _incr_count(full, "incr") == 0  # the oracle never does

    @pytest.mark.parametrize("seed", (0, 1))
    def test_mesh_sharded_incremental_matches_oracle(self, seed):
        """Same fuzz over the 8-device mesh-resident snapshot: the
        dirty-column rescore is shard-local (solver/incremental.py
        ``_rescore_sharded``) and must stay byte-identical to the
        full-rescore oracle on the SAME mesh — and to the single-chip
        incremental servicer."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        rng = np.random.RandomState(2000 + seed)
        state = _random_state(rng, n_nodes=6, n_pods=16,
                              with_quota=bool(seed))
        mesh = cluster_mesh(jax.devices())
        incr = ScorerServicer(
            mesh=mesh, mesh_resident=True, score_memo=False,
            score_incr_max_ratio=1.0,
        )
        full = ScorerServicer(
            mesh=mesh, mesh_resident=True, score_memo=False,
            score_incr=False,
        )
        chip = ScorerServicer(score_memo=False)
        for sv in (incr, full, chip):
            sv.sync(_full_sync_request(state))
        for cycle in range(8):
            req, _resized = _mutate(rng, state)
            for sv in (incr, full, chip):
                sv.sync(req)
            k = int(rng.choice([2, 8, 13]))
            a, b, c = _flat(incr, k), _flat(full, k), _flat(chip, k)
            assert a == b, f"seed={seed} cycle={cycle} mesh incr vs full"
            assert a == c, f"seed={seed} cycle={cycle} mesh vs single-chip"
        assert _incr_count(incr, "incr") > 0


class TestDirtyMechanics:
    def _servicer(self, **kw):
        rng = np.random.RandomState(7)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=True)
        sv = ScorerServicer(score_memo=False, **kw)
        sv.sync(_full_sync_request(state))
        return sv, state, rng

    def _node_delta(self, state, row=0, col=1, bump=7):
        prev = state["node_usage"].copy()
        state["node_usage"][row, col] += bump
        req = pb2.SyncRequest()
        req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"], prev))
        return req

    def test_dirty_sets_accumulate_across_syncs(self):
        """Several warm commits between Scores union their dirt; the
        one Score that follows incorporates all of it and clears."""
        sv, state, _rng = self._servicer()
        _flat(sv)  # populates the residency (result=full)
        sv.sync(self._node_delta(state, row=1))
        sv.sync(self._node_delta(state, row=3))
        sv.sync(self._node_delta(state, row=1, col=2))
        res = sv.state.score_residency()
        assert res is not None and res.dirty_nodes == {1, 3}
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)
        assert sv.state.score_residency().dirty_nodes == set()
        assert _incr_count(sv, "incr") == 1

    def test_quota_only_delta_rescores_zero_columns(self):
        """score_cycle reads no quota state: a quota-only delta stream
        advances the generation with ZERO dirty rows — the next Score
        is an incremental launch that touches no kernel at all, and the
        resident tensors are served as-is (still memo-missed: the
        snapshot id moved)."""
        sv, state, rng = self._servicer()
        _flat(sv)
        scores_before = sv.state.score_residency().scores
        sv.sync(_quota_delta(state, rng))
        assert sv.state.last_sync_path == "warm"
        res = sv.state.score_residency()
        assert res is not None
        assert res.dirty_nodes == set() and res.dirty_pods == set()
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)
        # the zero-dirty path reuses the very tensors — no new buffers
        assert sv.state.score_residency().scores is scores_before
        assert _incr_count(sv, "incr") == 1

    def test_node_fresh_flip_dirties_exactly_the_flipped_columns(self):
        sv, state, _rng = self._servicer()
        _flat(sv)
        flipped = state["node_fresh"].copy()
        flipped[2] = not flipped[2]
        flipped[4] = not flipped[4]
        state["node_fresh"] = flipped
        req = pb2.SyncRequest()
        req.nodes.metric_fresh.extend(bool(b) for b in flipped)
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"
        assert sv.state.score_residency().dirty_nodes == {2, 4}
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)

    def test_priority_churn_dirties_pods_whose_class_moved(self):
        """Raw priority feeds scoring only through the effective
        priority CLASS: a priority change inside the same band dirties
        nothing, a band crossing dirties that pod's row.  Every pod but
        pod 1 crosses into the next band; the dirty rows and the
        resident class column equal the scalar derivation and the cold
        build."""
        sv, state, rng = self._servicer()
        _flat(sv)
        old = state["pod_priority"]
        old_cls = [PriorityClass.from_priority_value(int(v)) for v in old]
        # one value base per class (NONE at 1000), indexed by class
        base = (9000, 7000, 5000, 3000, 1000)
        prio = np.asarray(
            [base[(int(c) + 1) % len(base)] for c in old_cls], np.int64
        ) + rng.randint(0, 1000, len(old))
        prio[1] = old[1] + 1 if old[1] % 1000 < 900 else old[1] - 1
        state["pod_priority"] = prio
        req = pb2.SyncRequest()
        req.pods.priority.extend(int(v) for v in prio)
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"
        new_cls = [PriorityClass.from_priority_value(int(v)) for v in prio]
        moved = {i for i, (a, b) in enumerate(zip(old_cls, new_cls)) if a != b}
        assert moved == set(range(len(old))) - {1}
        res = sv.state.score_residency()
        assert res is not None
        assert res.dirty_pods == moved
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        P = len(prio)
        resident = np.asarray(sv.state.snapshot().pods.priority_class)[:P]
        np.testing.assert_array_equal(resident, np.asarray(new_cls, np.int32))
        np.testing.assert_array_equal(
            resident, np.asarray(oracle.state.snapshot().pods.priority_class)[:P]
        )
        assert _flat(sv) == _flat(oracle)

    def test_ratio_gate_falls_back_to_full(self):
        sv, state, _rng = self._servicer(score_incr_max_ratio=0.0)
        _flat(sv)
        sv.sync(self._node_delta(state))
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)
        assert _incr_count(sv, "fallback") == 1
        assert _incr_count(sv, "incr") == 0
        # the full launch REFRESHED the residency: dirt cleared
        assert sv.state.score_residency().dirty_nodes == set()

    def test_full_tensor_reupload_drops_attribution(self):
        """A warm frame carrying a FULL tensor (row attribution lost)
        must drop the residency — the next Score full-rescores."""
        sv, state, _rng = self._servicer()
        _flat(sv)
        state["node_usage"] = state["node_usage"] + 1  # every cell moves
        req = pb2.SyncRequest()
        req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"]))
        assert req.nodes.usage.data  # rode full, not delta
        sv.sync(req)
        assert sv.state.last_sync_path == "warm"
        assert sv.state.score_residency() is None
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)
        assert _incr_count(sv, "full") == 2  # cold populate + re-populate

    def test_cycle_config_change_drops_residency(self):
        sv, state, _rng = self._servicer()
        _flat(sv)
        sv.sync(self._node_delta(state))
        # operator reconfig
        sv.cfg = CycleConfig(fit_scoring_strategy="MostAllocated")
        oracle = ScorerServicer(
            CycleConfig(fit_scoring_strategy="MostAllocated"),
            score_memo=False, score_incr=False,
        )
        oracle.sync(_full_sync_request(state))
        assert _flat(sv) == _flat(oracle)
        # the stale-config tensors were dropped, then re-populated
        assert _incr_count(sv, "incr") == 0
        assert _incr_count(sv, "full") == 2
        assert sv.state.score_residency().cfg == sv.cfg

    def test_opt_out_never_keeps_residency(self):
        sv, state, _rng = self._servicer(score_incr=False)
        _flat(sv)
        assert sv.state.score_residency() is None
        sv.sync(self._node_delta(state))
        _flat(sv)
        assert _incr_count(sv, "incr") == 0
        assert _incr_count(sv, "full") == 0  # engine fully out of play


class TestScoreMemoSeam:
    def _pair(self):
        rng = np.random.RandomState(11)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=False)
        sv = ScorerServicer()  # memo AND incremental engine on
        sv.sync(_full_sync_request(state))
        oracle = ScorerServicer(score_memo=False, score_incr=False)
        oracle.sync(_full_sync_request(state))
        return sv, oracle, state

    def _memo_hits(self, sv):
        return sv.telemetry.registry.get(
            "koord_scorer_score_memo_total", {"result": "hit"}
        ) or 0

    def _sync_both(self, sv, oracle, state):
        prev = state["node_usage"].copy()
        state["node_usage"][0, 1] += 3
        req = pb2.SyncRequest()
        req.nodes.usage.CopyFrom(numpy_to_tensor(state["node_usage"], prev))
        sv.sync(req)
        oracle.sync(pb2.SyncRequest.FromString(req.SerializeToString()))

    def test_pre_delta_memo_never_serves_after_bump(self):
        """The memo entry certifies the PRE-delta snapshot id; after
        the bump the Score must miss it and launch (incrementally) —
        serving the stale entry would hand out pre-delta scores under
        a post-delta id."""
        sv, oracle, state = self._pair()
        _flat(sv, 4)           # launch, populates memo for sid1
        assert _flat(sv, 4) == _flat(oracle, 4)  # memo-served, identical
        hits_before = self._memo_hits(sv)
        assert hits_before >= 1
        self._sync_both(sv, oracle, state)
        assert _flat(sv, 4) == _flat(oracle, 4)  # post-delta values
        assert self._memo_hits(sv) == hits_before  # no stale hit
        assert _incr_count(sv, "incr") == 1

    def test_incremental_launch_populates_memo_for_new_id(self):
        """The incremental launch's readback must publish the memo
        under the NEW snapshot id: a follow-up identical Score is
        memo-served, no second launch."""
        sv, oracle, state = self._pair()
        _flat(sv, 4)
        self._sync_both(sv, oracle, state)
        assert _flat(sv, 4) == _flat(oracle, 4)  # incremental launch
        hits = self._memo_hits(sv)
        incr_launches = _incr_count(sv, "incr")
        assert _flat(sv, 4) == _flat(oracle, 4)  # identical follow-up
        assert self._memo_hits(sv) == hits + 1
        assert _incr_count(sv, "incr") == incr_launches  # no new launch

    def test_owner_failure_fails_the_score_without_poisoning(
        self, monkeypatch
    ):
        """An incremental launch that raises must (a) fail its batch —
        no fallback hides the device fault — and (b) leave no residency
        behind, never a half-scattered tensor: the next Score rescores
        in full, exactly, and the engine then serves incrementally."""
        import pytest

        from koordinator_tpu.solver import incremental as incr_mod

        sv, oracle, state = self._pair()
        _flat(sv, 4)
        self._sync_both(sv, oracle, state)
        real = incr_mod.rescore_dirty
        calls = []

        def boom(*a, **kw):
            calls.append(1)
            raise RuntimeError("injected owner failure")

        monkeypatch.setattr(incr_mod, "rescore_dirty", boom)
        with pytest.raises(RuntimeError, match="injected owner failure"):
            _flat(sv, 4)
        assert calls and sv.state.score_residency() is None
        monkeypatch.setattr(incr_mod, "rescore_dirty", real)
        assert _flat(sv, 4) == _flat(oracle, 4)  # full rescore
        self._sync_both(sv, oracle, state)
        assert _flat(sv, 4) == _flat(oracle, 4)
        assert _incr_count(sv, "incr") == 1


class TestFollowerIncremental:
    def test_follower_applies_frames_incrementally_with_byte_parity(self):
        """ISSUE 8 closure: a replication follower applies streamed
        delta frames through the same stage/commit seam, so its
        resident score tensors advance incrementally too — the
        follower must NOT pay the full rescores the leader skipped,
        and its Score replies must stay byte-identical to the
        leader's after every applied frame."""
        from koordinator_tpu.replication.follower import (
            APPLIED,
            FollowerServicer,
            ReplicaApplier,
        )
        from test_replication import _capture_frames, _full_frame

        rng = np.random.RandomState(17)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=False)
        leader = ScorerServicer(score_memo=False)
        frames = _capture_frames(leader)
        leader.sync(_full_sync_request(state))
        follower = FollowerServicer(score_memo=False)
        applier = ReplicaApplier(follower)
        assert applier.offer(_full_frame(leader)) == APPLIED
        # both tiers score once: the residency exists on each side
        assert _flat(leader, 6) == _flat(follower, 6)
        for step in range(5):
            prev = state["node_usage"].copy()
            state["node_usage"][step % 6, 2] += 5
            req = pb2.SyncRequest()
            req.nodes.usage.CopyFrom(
                numpy_to_tensor(state["node_usage"], prev)
            )
            leader.sync(req)
            assert applier.offer(frames[-1]) == APPLIED
            assert follower.state.last_sync_path == "warm"
            assert follower.snapshot_id() == leader.snapshot_id()
            assert _flat(follower, 6) == _flat(leader, 6), f"step={step}"
        # the follower's Scores after the first ran INCREMENTALLY
        assert _incr_count(follower, "incr") == 5
        assert _incr_count(follower, "full") == 1
        res = follower.state.score_residency()
        assert res is not None and res.dirty_nodes == set()

    def test_full_resync_frame_drops_follower_residency(self):
        from koordinator_tpu.replication.follower import (
            APPLIED,
            FollowerServicer,
            ReplicaApplier,
        )
        from test_replication import _full_frame

        rng = np.random.RandomState(19)
        state = _random_state(rng, n_nodes=5, n_pods=10, with_quota=False)
        leader = ScorerServicer(score_memo=False)
        leader.sync(_full_sync_request(state))
        follower = FollowerServicer(score_memo=False)
        applier = ReplicaApplier(follower)
        assert applier.offer(_full_frame(leader)) == APPLIED
        _flat(follower, 4)
        assert follower.state.score_residency() is not None
        # a reset frame swaps in a FRESH ResidentState: no stale score
        # tensors may survive the swap
        assert applier.offer(
            dataclasses.replace(_full_frame(leader), generation=0)
        ) in (APPLIED,)
        assert follower.state.score_residency() is None
        assert _flat(follower, 4) == _flat(leader, 4)


class TestMaskedTopK:
    """solver/topk.py: the packed-f64 serving top-k must be
    bit-identical to ``lax.top_k`` over the masked i64 tensor — values,
    indices, ordering AND ties — and the static bound it relies on must
    actually hold for score_cycle's output."""

    def test_packed_matches_integer_topk_with_ties(self):
        from jax import lax
        import jax.numpy as jnp

        from koordinator_tpu.solver import masked_top_k

        rng = np.random.RandomState(31)
        # heavy duplication forces the tie-break path; a column of
        # all-infeasible rows covers the sentinel ordering
        scores = jnp.asarray(
            rng.randint(0, 7, (40, 33)).astype(np.int64) * 50
        )
        feasible = jnp.asarray(rng.rand(40, 33) > 0.3)
        feasible = feasible.at[7, :].set(False)
        masked = jnp.where(feasible, scores, jnp.iinfo(jnp.int64).min)
        for k in (1, 5, 33):
            want_s, want_i = lax.top_k(masked, k)
            got_s, got_i = masked_top_k(scores, feasible, k=k, hi=300)
            np.testing.assert_array_equal(np.asarray(got_s),
                                          np.asarray(want_s), err_msg=f"k={k}")
            np.testing.assert_array_equal(np.asarray(got_i),
                                          np.asarray(want_i), err_msg=f"k={k}")

    def test_unpackable_bound_takes_integer_path_exactly(self):
        from jax import lax
        import jax.numpy as jnp

        from koordinator_tpu.solver import masked_top_k

        rng = np.random.RandomState(37)
        scores = jnp.asarray(
            rng.randint(-(2 ** 60), 2 ** 60, (8, 16)).astype(np.int64)
        )
        feasible = jnp.asarray(rng.rand(8, 16) > 0.5)
        masked = jnp.where(feasible, scores, jnp.iinfo(jnp.int64).min)
        want_s, want_i = lax.top_k(masked, 4)
        got_s, got_i = masked_top_k(scores, feasible, k=4, hi=2 ** 60)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))

    def test_out_of_bound_scores_fall_to_integer_branch_exactly(self):
        """The wire accepts arbitrary int64, so a hostile tensor can
        push scores past the static hi bound (a negative requested
        breaks least_requested_score's clamp) — the device-side
        in-bound check must route those launches to the integer branch
        of the same program, bit-exactly, instead of letting distinct
        i64 scores collapse onto one f32 rank."""
        from jax import lax
        import jax.numpy as jnp

        from koordinator_tpu.solver import masked_top_k

        rng = np.random.RandomState(41)
        # values far past 2^24 AND negative feasible values: both
        # violations of the fast path's rank contract
        scores = jnp.asarray(
            rng.randint(-(2 ** 40), 2 ** 40, (16, 24)).astype(np.int64)
        )
        feasible = jnp.asarray(rng.rand(16, 24) > 0.4)
        masked = jnp.where(feasible, scores, jnp.iinfo(jnp.int64).min)
        want_s, want_i = lax.top_k(masked, 6)
        got_s, got_i = masked_top_k(scores, feasible, k=6, hi=200)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
        # out-of-bound values hidden behind infeasibility do NOT demote:
        # rank 0 covers them regardless of their wild values
        wild = jnp.where(feasible, jnp.clip(scores, 0, 200),
                         jnp.int64(2 ** 40))
        masked2 = jnp.where(feasible, wild, jnp.iinfo(jnp.int64).min)
        want_s, want_i = lax.top_k(masked2, 6)
        got_s, got_i = masked_top_k(wild, feasible, k=6, hi=200)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))

    def test_score_upper_bound_holds_on_fuzzed_snapshots(self):
        """The packed path's INVARIANT: score_cycle's combined scores
        sit in [0, score_upper_bound(cfg)] — every term clamps to
        [0, MAX_NODE_SCORE] per plugin weight.  A future scoring term
        that breaks this must widen score_upper_bound."""
        from koordinator_tpu.solver import score_cycle, score_upper_bound
        from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG

        hi = score_upper_bound(DEFAULT_CYCLE_CONFIG)
        assert hi > 0
        for seed in range(3):
            rng = np.random.RandomState(4000 + seed)
            state = _random_state(rng, n_nodes=7, n_pods=18,
                                  with_quota=bool(seed % 2))
            sv = ScorerServicer(score_memo=False)
            sv.sync(_full_sync_request(state))
            scores, _ = score_cycle(sv.state.snapshot(),
                                    DEFAULT_CYCLE_CONFIG)
            s = np.asarray(scores)
            assert s.min() >= 0 and s.max() <= hi, (seed, s.min(), s.max())


class TestIncrementalRetraceBudget:
    def test_warm_delta_score_stream_is_retrace_free(self):
        """The ISSUE 9 acceptance: a warm delta-Sync -> incremental
        Score stream holds ZERO jit cache misses after one warm-up —
        the dirty sets ride bucket-padded index vectors, so varying
        dirty composition mints no new compiled shapes."""
        from koordinator_tpu.analysis import retrace_guard

        rng = np.random.RandomState(23)
        state = _random_state(rng, n_nodes=6, n_pods=12, with_quota=False)
        # ratio gate open: 3 dirty nodes of a 6-node table would trip
        # the default cost gate, and this test times the KERNEL path
        sv = ScorerServicer(score_memo=False, score_incr_max_ratio=1.0)
        sv.sync(_full_sync_request(state))

        def step(rows):
            prev = state["node_usage"].copy()
            for r in rows:
                state["node_usage"][r, 1] += 1
            req = pb2.SyncRequest()
            req.nodes.usage.CopyFrom(
                numpy_to_tensor(state["node_usage"], prev)
            )
            sv.sync(req)
            assert sv.state.last_sync_path == "warm"
            return _flat(sv, 3)

        _flat(sv, 3)       # warm-up: full score + residency
        step([0])          # warm-up: incremental kernel compile
        with retrace_guard(budget=0) as counter:
            for i in range(4):
                # 1..3 dirty nodes per delta: same pad bucket, and the
                # dirty-set union must not leak a count into any trace
                step([i % 6, (i + 1) % 6, (i + 2) % 6][: 1 + i % 3])
        assert counter.traces == 0 and counter.compiles == 0
        assert _incr_count(sv, "incr") == 5
