"""ISSUE 15: the fused scoring-term registry.

Three policies land as fused tensor terms in the ONE pods x nodes
launch (solver/terms.py): Gavel-style heterogeneity (throughput-matrix
gather), Synergy-style CPU/mem sensitivity profiles, and a bin-packing
objective + headroom mask.  Covered here:

* plain-numpy oracle parity — every term's device contribution is
  re-derived by an independent numpy implementation, cell for cell,
  on fuzzed snapshots with gang/quota interaction;
* Assign parity on every Assign engine — ``run_cycle`` with a
  term-enabled config, the dense Pallas kernel and the round-based
  shard path fed the device term tensors through their extras seam all
  equal ``greedy_assign`` fed the numpy oracle's tensors, bit for bit;
* mesh parity on {1, 8} devices — the mesh-resident servicer's warm
  term-delta stream is byte-identical to the single-chip servicer's;
* dirty-set attribution — sensitivity deltas dirty exactly the touched
  pod rows, a throughput-matrix delta dirties exactly the nodes of the
  touched accelerator type, accel/workload flips dirty their own rows,
  and the warm stream holds ZERO jit cache misses with all terms on;
* the term-aware serving bound — ``score_upper_bound`` covers the new
  contributions so the f32-exact top-k fast path stays exact, and
  ``masked_top_k_host`` (the brownout cache's host twin) is
  bit-identical to the device path.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from koordinator_tpu.analysis import retrace_guard
from koordinator_tpu.bridge.codegen import pb2
from koordinator_tpu.bridge.server import ScorerServicer
from koordinator_tpu.bridge.state import numpy_to_tensor
from koordinator_tpu.config import (
    CycleConfig,
    HeterogeneityTermArgs,
    PackingTermArgs,
    SensitivityTermArgs,
)
from koordinator_tpu.model import resources as res
from koordinator_tpu.model.snapshot import MAX_NODE_SCORE, encode_snapshot
from koordinator_tpu.parallel import greedy_assign_waves, make_mesh
from koordinator_tpu.solver import (
    greedy_assign,
    masked_top_k,
    run_cycle,
    score_cycle,
    score_upper_bound,
)
from koordinator_tpu.solver.pallas_dense import greedy_assign_dense
from koordinator_tpu.solver.terms import (
    default_term_config,
    term_extras,
    term_names,
    terms_upper_bound,
)
from koordinator_tpu.solver.topk import masked_top_k_host

R = res.NUM_RESOURCES

HEADROOM = {"cpu": 96, "memory": 97}

ALL_TERMS = default_term_config(packing_headroom=HEADROOM)


def _cfg_for(term: str) -> CycleConfig:
    if term == "heterogeneity":
        return CycleConfig(heterogeneity=HeterogeneityTermArgs(weight=2))
    if term == "sensitivity":
        return CycleConfig(sensitivity=SensitivityTermArgs(weight=3))
    if term == "packing":
        return CycleConfig(
            packing=PackingTermArgs(weight=2, headroom=HEADROOM)
        )
    return ALL_TERMS


# ---------------------------------------------------------------------------
# the plain-numpy oracle: an INDEPENDENT restatement of each term's
# integer math, computed from the snapshot's own padded tensors
# ---------------------------------------------------------------------------


def np_term_tensors(snap, cfg):
    """(extra_scores i64[P, N], extra_mask bool[P, N]) re-derived with
    numpy only — the reference the fused device terms must match."""
    nalloc = np.asarray(snap.nodes.allocatable, np.int64)
    nreq = np.asarray(snap.nodes.requested, np.int64)
    nuse = np.asarray(snap.nodes.usage, np.int64)
    preq = np.asarray(snap.pods.requests, np.int64)
    P, N = preq.shape[0], nalloc.shape[0]
    scores = np.zeros((P, N), np.int64)
    mask = np.ones((P, N), bool)

    def clip_term(raw, weight):
        return int(weight) * np.clip(
            raw.astype(np.int64), 0, MAX_NODE_SCORE
        )

    if cfg.heterogeneity is not None and snap.throughput is not None:
        tput = np.asarray(snap.throughput, np.int64)
        C, A = tput.shape
        wc = (
            np.clip(np.asarray(snap.pods.workload_class, np.int64), 0, C - 1)
            if snap.pods.workload_class is not None
            else np.zeros(P, np.int64)
        )
        ac = (
            np.clip(np.asarray(snap.nodes.accel_type, np.int64), 0, A - 1)
            if snap.nodes.accel_type is not None
            else np.zeros(N, np.int64)
        )
        scores = scores + clip_term(
            tput[wc[:, None], ac[None, :]], cfg.heterogeneity.weight
        )
    if cfg.sensitivity is not None and snap.pods.sensitivity is not None:
        sens = np.clip(
            np.asarray(snap.pods.sensitivity, np.int64), 0, MAX_NODE_SCORE
        )
        safe = np.where(nalloc == 0, 1, nalloc)
        occ = np.clip(nuse * MAX_NODE_SCORE // safe, 0, MAX_NODE_SCORE)
        occ = np.where(nalloc == 0, 0, occ)
        contention = (sens @ occ.T) // np.maximum(
            sens.sum(axis=-1), 1
        )[:, None]
        scores = scores + clip_term(
            MAX_NODE_SCORE - contention, cfg.sensitivity.weight
        )
    if cfg.packing is not None:
        w = np.asarray(
            res.weights_vector(dict(cfg.packing.resource_weights)), np.int64
        )
        post = nreq[None, :, :] + preq[:, None, :]
        safe = np.where(nalloc == 0, 1, nalloc)[None, :, :]
        per_res = np.minimum(post, nalloc[None]) * MAX_NODE_SCORE // safe
        per_res = np.where(nalloc[None] == 0, 0, per_res)
        wsum = int(w.sum())
        weighted = (
            (per_res * w).sum(axis=-1) // max(wsum, 1)
            if wsum
            else np.zeros((P, N), np.int64)
        )
        scores = scores + clip_term(weighted, cfg.packing.weight)
        head = np.asarray(
            res.weights_vector(dict(cfg.packing.headroom)), np.int64
        )
        if (head > 0).any():
            limited = head[None, None, :] > 0
            ok = post * 100 <= head[None, None, :] * nalloc[None, :, :]
            mask = mask & np.all(np.where(limited, ok, True), axis=-1)
    return scores, mask


def _term_lists(rng, n_nodes, n_pods, classes=3, accels=2):
    """Generator-style node/pod dict lists with gang/quota interaction
    plus the term columns, and the [C, A] throughput matrix."""
    nodes = [
        dict(
            name=f"n{i}",
            allocatable={"cpu": int(rng.integers(4000, 32000)),
                         "memory": int(rng.integers(4096, 65536)),
                         "pods": 64},
            requested={"cpu": int(rng.integers(0, 3000)),
                       "memory": int(rng.integers(0, 3000))},
            usage={"cpu": int(rng.integers(0, 6000)),
                   "memory": int(rng.integers(0, 6000))},
            metric_fresh=bool(rng.random() > 0.15),
            accel_type=int(rng.integers(0, accels)),
        )
        for i in range(n_nodes)
    ]
    gangs = [dict(name="g0", min_member=2), dict(name="g1", min_member=3)]
    quotas = [
        dict(name="q0",
             runtime={"cpu": 40000, "memory": 80000},
             used={"cpu": int(rng.integers(0, 8000))}),
        dict(name="q1",
             runtime={"cpu": 20000, "memory": 30000},
             used={"cpu": int(rng.integers(0, 8000))}),
    ]
    pods = [
        dict(
            name=f"p{i}",
            requests={"cpu": int(rng.integers(100, 3000)),
                      "memory": int(rng.integers(128, 3000))},
            priority=int(rng.integers(3000, 9999)),
            gang=(
                ["g0", "g1"][int(rng.integers(0, 2))]
                if rng.random() > 0.6 else None
            ),
            quota=(
                ["q0", "q1"][int(rng.integers(0, 2))]
                if rng.random() > 0.4 else None
            ),
            workload_class=int(rng.integers(0, classes)),
            sensitivity={"cpu": int(rng.integers(0, 101)),
                         "memory": int(rng.integers(0, 101))},
        )
        for i in range(n_pods)
    ]
    tput = rng.integers(0, 101, (classes, accels)).astype(np.int64)
    return nodes, pods, gangs, quotas, tput


def _term_snapshot(seed, n_nodes=9, n_pods=14):
    rng = np.random.default_rng(seed)
    nodes, pods, gangs, quotas, tput = _term_lists(rng, n_nodes, n_pods)
    return encode_snapshot(nodes, pods, gangs, quotas, throughput=tput)


class TestNumpyOracleParity:
    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize(
        "term", ["heterogeneity", "sensitivity", "packing", "all"]
    )
    def test_fused_term_matches_numpy_oracle(self, seed, term):
        snap = _term_snapshot(seed)
        cfg = _cfg_for(term)
        s, f = map(np.asarray, score_cycle(snap, cfg))
        s0, f0 = map(np.asarray, score_cycle(snap, CycleConfig()))
        xs, xm = np_term_tensors(snap, cfg)
        np.testing.assert_array_equal(s, s0 + xs)
        np.testing.assert_array_equal(f, f0 & xm)

    def test_term_extras_match_numpy_oracle(self):
        snap = _term_snapshot(0)
        xs, xm = term_extras(snap, ALL_TERMS)
        ns, nm = np_term_tensors(snap, ALL_TERMS)
        np.testing.assert_array_equal(np.asarray(xs), ns)
        np.testing.assert_array_equal(np.asarray(xm), nm)

    def test_missing_term_data_is_inert(self):
        # terms enabled but NO term tensors synced: the cycle must not
        # fault and must score exactly like the untermed config (the
        # packing term needs no side tensors, so exclude it)
        rng = np.random.default_rng(3)
        nodes, pods, gangs, quotas, _ = _term_lists(rng, 6, 8)
        for nd in nodes:
            nd.pop("accel_type")
        for pd in pods:
            pd.pop("workload_class")
            pd.pop("sensitivity")
        snap = encode_snapshot(nodes, pods, gangs, quotas)
        cfg = CycleConfig(
            heterogeneity=HeterogeneityTermArgs(),
            sensitivity=SensitivityTermArgs(),
        )
        s, f = map(np.asarray, score_cycle(snap, cfg))
        s0, f0 = map(np.asarray, score_cycle(snap, CycleConfig()))
        np.testing.assert_array_equal(s, s0)
        np.testing.assert_array_equal(f, f0)


class TestAssignWaveParity:
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("engine", ["run_cycle", "dense", "waves"])
    def test_run_cycle_terms_equal_oracle_extras(self, seed, engine):
        snap = _term_snapshot(seed, n_nodes=10, n_pods=18)
        if engine == "waves":
            # the shard path is never served a gang (no rollback)
            snap = dataclasses.replace(snap, pods=dataclasses.replace(
                snap.pods,
                gang_id=np.full(snap.pods.gang_id.shape, -1, np.int32),
            ))
        if engine == "run_cycle":
            got = run_cycle(snap, ALL_TERMS)
        else:
            t_scores, t_mask = term_extras(snap, ALL_TERMS)
            extras = dict(extra_mask=t_mask, extra_scores=t_scores)
            if engine == "dense":
                got = greedy_assign_dense(
                    snap, CycleConfig(), interpret=True, **extras
                )
            else:
                got, _ = greedy_assign_waves(
                    snap, make_mesh(jax.devices()[:2]), CycleConfig(),
                    **extras,
                )
        xs, xm = np_term_tensors(snap, ALL_TERMS)
        want = greedy_assign(
            snap, CycleConfig(),
            extra_mask=jnp.asarray(xm), extra_scores=jnp.asarray(xs),
        )
        np.testing.assert_array_equal(
            np.asarray(got.assignment), np.asarray(want.assignment)
        )
        np.testing.assert_array_equal(
            np.asarray(got.status), np.asarray(want.status)
        )


# ---------------------------------------------------------------------------
# servicer streams: mesh parity + dirty attribution + retrace guard
# ---------------------------------------------------------------------------


def _full_term_sync(state) -> "pb2.SyncRequest":
    req = pb2.SyncRequest()
    req.nodes.allocatable.CopyFrom(numpy_to_tensor(state["nalloc"]))
    req.nodes.requested.CopyFrom(numpy_to_tensor(state["nreq"]))
    req.nodes.usage.CopyFrom(numpy_to_tensor(state["nuse"]))
    req.nodes.metric_fresh.extend(bool(b) for b in state["fresh"])
    req.nodes.accel_type.extend(int(v) for v in state["accel"])
    req.pods.requests.CopyFrom(numpy_to_tensor(state["preq"]))
    req.pods.estimated.CopyFrom(numpy_to_tensor(state["pest"]))
    req.pods.priority.extend(int(v) for v in state["prio"])
    req.pods.gang_id.extend(int(v) for v in state["gang"])
    req.pods.quota_id.extend(int(v) for v in state["quota"])
    req.pods.workload_class.extend(int(v) for v in state["wclass"])
    req.pods.sensitivity.CopyFrom(numpy_to_tensor(state["sens"]))
    req.gangs.min_member.extend([2, 3])
    req.quotas.runtime.CopyFrom(numpy_to_tensor(state["qrt"]))
    req.quotas.used.CopyFrom(numpy_to_tensor(state["quse"]))
    req.quotas.limited.CopyFrom(numpy_to_tensor(state["qlim"]))
    req.terms.throughput.CopyFrom(numpy_to_tensor(state["tput"]))
    return req


def _term_state(rng, n_nodes=8, n_pods=12, classes=3, accels=2):
    sens = np.zeros((n_pods, R), np.int64)
    sens[:, 0] = rng.integers(0, 101, n_pods)
    sens[:, 1] = rng.integers(0, 101, n_pods)
    return {
        "nalloc": rng.integers(4000, 64000, (n_nodes, R)).astype(np.int64),
        "nreq": rng.integers(0, 2000, (n_nodes, R)).astype(np.int64),
        "nuse": rng.integers(0, 3000, (n_nodes, R)).astype(np.int64),
        "fresh": rng.random(n_nodes) > 0.2,
        "accel": (np.arange(n_nodes) % accels).astype(np.int64),
        "preq": rng.integers(1, 4000, (n_pods, R)).astype(np.int64),
        "pest": rng.integers(1, 4000, (n_pods, R)).astype(np.int64),
        "prio": rng.integers(0, 9999, n_pods).astype(np.int64),
        "gang": np.where(
            rng.random(n_pods) > 0.5, rng.integers(0, 2, n_pods), -1
        ).astype(np.int64),
        "quota": np.where(
            rng.random(n_pods) > 0.4, rng.integers(0, 2, n_pods), -1
        ).astype(np.int64),
        "wclass": rng.integers(0, classes, n_pods).astype(np.int64),
        "sens": sens,
        "qrt": rng.integers(5000, 500000, (2, R)).astype(np.int64),
        "quse": rng.integers(0, 4000, (2, R)).astype(np.int64),
        "qlim": (rng.random((2, R)) > 0.5).astype(np.int64),
        "tput": rng.integers(0, 101, (classes, accels)).astype(np.int64),
    }


def _flat(sv, k=8):
    return sv.score(pb2.ScoreRequest(
        snapshot_id=sv.snapshot_id(), top_k=k, flat=True
    )).flat.SerializeToString()


def _term_mutations(rng, state):
    """One warm term-touching mutation; returns the delta SyncRequest."""
    req = pb2.SyncRequest()
    kind = int(rng.integers(0, 4))
    if kind == 0:  # sensitivity drift
        prev = state["sens"].copy()
        rows = rng.choice(
            state["sens"].shape[0], int(rng.integers(1, 4)), replace=False
        )
        for r_ in rows:
            state["sens"][r_, 0] = int(rng.integers(0, 101))
        req.pods.sensitivity.CopyFrom(
            numpy_to_tensor(state["sens"], prev)
        )
    elif kind == 1:  # throughput-matrix update (one (class, accel) cell
        # — dirt stays the one accel type's node columns, under the
        # incremental engine's dirty-ratio gate)
        prev = state["tput"].copy()
        c = int(rng.integers(0, state["tput"].shape[0]))
        a = int(rng.integers(0, state["tput"].shape[1]))
        state["tput"][c, a] = int(rng.integers(0, 101))
        req.terms.throughput.CopyFrom(
            numpy_to_tensor(state["tput"], prev)
        )
    elif kind == 2:  # accel-type flip
        n = int(rng.integers(0, len(state["accel"])))
        state["accel"][n] = (state["accel"][n] + 1) % 2
        req.nodes.accel_type.extend(int(v) for v in state["accel"])
    else:  # workload-class flip + a usage tick (mixed frame)
        p = int(rng.integers(0, len(state["wclass"])))
        state["wclass"][p] = (state["wclass"][p] + 1) % 3
        req.pods.workload_class.extend(int(v) for v in state["wclass"])
        prev = state["nuse"].copy()
        state["nuse"][int(rng.integers(0, len(state["fresh"]))), 0] += 7
        req.nodes.usage.CopyFrom(numpy_to_tensor(state["nuse"], prev))
    return req


class TestMeshParity:
    @pytest.mark.parametrize("devices", [1, 8])
    @pytest.mark.parametrize("seed", range(2))
    def test_warm_term_stream_mesh_vs_single_chip(self, devices, seed):
        from koordinator_tpu.parallel import cluster_mesh

        rng = np.random.default_rng(seed)
        state = _term_state(rng)
        cfg = ALL_TERMS
        mesh_kw = {}
        if devices > 1:
            mesh_kw = dict(
                mesh=cluster_mesh(jax.devices()[:devices]),
                mesh_resident=True,
            )
        sharded = ScorerServicer(cfg=cfg, score_memo=False, **mesh_kw)
        oracle = ScorerServicer(
            cfg=cfg, score_memo=False, score_incr=False
        )
        raw = _full_term_sync(state).SerializeToString()
        for sv in (sharded, oracle):
            sv.sync(pb2.SyncRequest.FromString(raw))
        assert _flat(sharded) == _flat(oracle)
        for _ in range(6):
            raw = _term_mutations(rng, state).SerializeToString()
            for sv in (sharded, oracle):
                sv.sync(pb2.SyncRequest.FromString(raw))
                assert sv.state.last_sync_path == "warm"
            assert _flat(sharded) == _flat(oracle)
        # the engine actually rescored incrementally (not full fallback)
        incr = sharded.telemetry.registry.get(
            "koord_scorer_score_incr_total", {"result": "incr"}
        ) or 0
        assert incr >= 4


class TestDirtyAttribution:
    def _pair(self, seed=0):
        rng = np.random.default_rng(seed)
        state = _term_state(rng)
        incr = ScorerServicer(cfg=ALL_TERMS, score_memo=False)
        full = ScorerServicer(
            cfg=ALL_TERMS, score_memo=False, score_incr=False
        )
        raw = _full_term_sync(state).SerializeToString()
        for sv in (incr, full):
            sv.sync(pb2.SyncRequest.FromString(raw))
        assert _flat(incr) == _flat(full)
        return state, incr, full

    def _warm(self, state_req, incr, full):
        raw = state_req.SerializeToString()
        for sv in (incr, full):
            sv.sync(pb2.SyncRequest.FromString(raw))
            assert sv.state.last_sync_path == "warm"

    def test_sensitivity_delta_dirties_exactly_touched_pods(self):
        state, incr, full = self._pair()
        prev = state["sens"].copy()
        state["sens"][3, 0] += 9
        state["sens"][5, 1] += 4
        req = pb2.SyncRequest()
        req.pods.sensitivity.CopyFrom(numpy_to_tensor(state["sens"], prev))
        self._warm(req, incr, full)
        res_st = incr.state.score_residency()
        assert res_st.dirty_pods == {3, 5}
        assert res_st.dirty_nodes == set()
        assert _flat(incr) == _flat(full)

    def test_throughput_delta_dirties_only_matching_accel_nodes(self):
        state, incr, full = self._pair(1)
        prev = state["tput"].copy()
        state["tput"][1, 1] += 5  # accel type 1's column
        req = pb2.SyncRequest()
        req.terms.throughput.CopyFrom(numpy_to_tensor(state["tput"], prev))
        self._warm(req, incr, full)
        res_st = incr.state.score_residency()
        want = set(np.flatnonzero(state["accel"] == 1).tolist())
        assert res_st.dirty_nodes == want
        assert res_st.dirty_pods == set()
        assert _flat(incr) == _flat(full)

    def test_accel_and_workload_flips_dirty_their_rows(self):
        state, incr, full = self._pair(2)
        state["accel"][2] = (state["accel"][2] + 1) % 2
        req = pb2.SyncRequest()
        req.nodes.accel_type.extend(int(v) for v in state["accel"])
        self._warm(req, incr, full)
        assert incr.state.score_residency().dirty_nodes == {2}
        assert _flat(incr) == _flat(full)
        state["wclass"][4] = (state["wclass"][4] + 1) % 3
        req = pb2.SyncRequest()
        req.pods.workload_class.extend(int(v) for v in state["wclass"])
        self._warm(req, incr, full)
        assert incr.state.score_residency().dirty_pods == {4}
        assert _flat(incr) == _flat(full)

    def test_warm_term_stream_holds_zero_jit_misses(self):
        rng = np.random.default_rng(7)
        state, incr, full = self._pair(7)
        # warm-up: one mutation of each kind compiles every bucket
        for kind_seed in range(4):
            self._warm(_term_mutations(
                np.random.default_rng(100 + kind_seed), state
            ), incr, full)
            assert _flat(incr) == _flat(full)
        with retrace_guard(budget=0):
            for _ in range(6):
                self._warm(_term_mutations(rng, state), incr, full)
                assert _flat(incr) == _flat(full)

    def test_first_term_column_appearance_goes_cold(self):
        # a snapshot synced WITHOUT accel gaining it later changes the
        # resident pytree structure: the commit must drop residency
        # (cold) instead of warm-patching a None leaf
        rng = np.random.default_rng(9)
        state = _term_state(rng)
        req = _full_term_sync(state)
        req.nodes.ClearField("accel_type")
        sv = ScorerServicer(cfg=ALL_TERMS, score_memo=False)
        sv.sync(req)
        _flat(sv)
        late = pb2.SyncRequest()
        late.nodes.accel_type.extend(int(v) for v in state["accel"])
        sv.sync(late)
        assert sv.state.last_sync_path == "cold"


class TestServingBound:
    @pytest.mark.parametrize("seed", range(3))
    def test_scores_stay_under_term_aware_bound(self, seed):
        snap = _term_snapshot(seed)
        s, f = map(np.asarray, score_cycle(snap, ALL_TERMS))
        hi = score_upper_bound(ALL_TERMS)
        assert hi == score_upper_bound(CycleConfig()) + terms_upper_bound(
            ALL_TERMS
        )
        assert s[f].max(initial=0) <= hi
        assert s[f].min(initial=0) >= 0

    @pytest.mark.parametrize("seed", range(3))
    def test_masked_top_k_fast_path_exact_with_terms(self, seed):
        snap = _term_snapshot(seed)
        s, f = score_cycle(snap, ALL_TERMS)
        k = 6
        ts, ti = masked_top_k(s, f, k=k, hi=score_upper_bound(ALL_TERMS))
        masked = jnp.where(f, s, jnp.iinfo(jnp.int64).min)
        want_ts, want_ti = lax.top_k(masked, k)
        np.testing.assert_array_equal(np.asarray(ts), np.asarray(want_ts))
        np.testing.assert_array_equal(np.asarray(ti), np.asarray(want_ti))

    @pytest.mark.parametrize("seed", range(3))
    def test_masked_top_k_host_bit_identical(self, seed):
        snap = _term_snapshot(seed)
        s, f = score_cycle(snap, ALL_TERMS)
        for k in (1, 5, int(np.asarray(s).shape[1])):
            ts, ti = masked_top_k(
                s, f, k=k, hi=score_upper_bound(ALL_TERMS)
            )
            hts, hti = masked_top_k_host(np.asarray(s), np.asarray(f), k)
            np.testing.assert_array_equal(hts, np.asarray(ts))
            np.testing.assert_array_equal(hti, np.asarray(ti))

    def test_masked_top_k_host_extreme_values(self):
        # ties break toward the lower index; i64 extremes must not
        # overflow the host ranking (the uint64-bias trick)
        s = np.asarray([[5, 5, np.iinfo(np.int64).max,
                         np.iinfo(np.int64).min, 5]], np.int64)
        f = np.asarray([[True, True, True, True, False]])
        ts, ti = masked_top_k_host(s, f, 4)
        assert ti.tolist() == [[2, 0, 1, 3]]
        dts, dti = lax.top_k(
            jnp.where(jnp.asarray(f), jnp.asarray(s),
                      jnp.iinfo(jnp.int64).min), 4
        )
        np.testing.assert_array_equal(ts, np.asarray(dts))
        np.testing.assert_array_equal(ti, np.asarray(dti))


class TestTermConfigSurface:
    def test_term_configs_hash_and_freeze(self):
        a = PackingTermArgs(headroom={"cpu": 90, "memory": 95})
        b = PackingTermArgs(
            headroom=(("cpu", 90), ("memory", 95))
        )
        assert a == b and hash(a) == hash(b)
        assert hash(ALL_TERMS) == hash(
            default_term_config(packing_headroom=HEADROOM)
        )

    def test_term_names_and_bounds(self):
        assert term_names(CycleConfig()) == ()
        assert term_names(ALL_TERMS) == (
            "heterogeneity", "sensitivity", "packing"
        )
        assert terms_upper_bound(CycleConfig()) == 0
        assert terms_upper_bound(ALL_TERMS) == 3 * MAX_NODE_SCORE

    def test_term_metric_counts_per_launch(self):
        rng = np.random.default_rng(11)
        state = _term_state(rng)
        sv = ScorerServicer(cfg=ALL_TERMS, score_memo=False)
        sv.sync(_full_term_sync(state))
        _flat(sv)
        reg = sv.telemetry.registry
        for term in ("heterogeneity", "sensitivity", "packing"):
            assert reg.get(
                "koord_scorer_term_total", {"term": term}
            ) == 1.0


class TestTermTraceEvents:
    def _cfg(self, seed=5):
        from koordinator_tpu.harness.trace import TERM_MIX, TraceConfig

        return TraceConfig(
            seed=seed, nodes=6, pod_slots=24, gangs=2, gang_min_member=3,
            events=8, mix=TERM_MIX, accel_types=2, workload_classes=3,
        )

    def test_term_trace_digest_pinned_per_seed(self):
        from koordinator_tpu.harness.trace import generate_trace

        a = generate_trace(self._cfg())
        assert a.digest() == generate_trace(self._cfg()).digest()
        assert a.digest() != generate_trace(self._cfg(seed=6)).digest()
        kinds = {e.kind for e in a.events}
        assert kinds & {"throughput_update", "sensitivity_drift"}

    def test_term_trace_export_import_round_trip(self):
        from koordinator_tpu.harness.trace import (
            export_trace,
            generate_trace,
            import_trace,
        )

        trace = generate_trace(self._cfg())
        rebuilt = import_trace(export_trace(trace))
        assert rebuilt.digest() == trace.digest()

    def test_term_trace_replays_with_parity_and_zero_retraces(self):
        from koordinator_tpu.harness.trace import TraceReplay, generate_trace

        trace = generate_trace(self._cfg())
        cfg = default_term_config(packing_headroom=HEADROOM)
        # no explicit oracle_kw: TraceReplay defaults the oracle's cfg
        # from engine_kw, so term-enabled replays are parity-consistent
        # out of the box
        report = TraceReplay(trace, engine_kw=dict(cfg=cfg)).run()
        assert report.retraces == 0
        assert report.parity_checks == len(trace.events) + 1

    def test_chaos_trace_gate_runs_with_terms(self, tmp_path):
        # the chaos x trace gate (ISSUE 13) exercises the new terms on
        # the warm delta path: a launch-failure burst mid-replay over a
        # TERM_MIX trace still converges to oracle parity with the
        # three-term config on BOTH sides
        from koordinator_tpu.harness.chaos import ChaosTraceReplay
        from koordinator_tpu.harness.trace import generate_trace

        trace = generate_trace(self._cfg())
        cfg = default_term_config(packing_headroom=HEADROOM)
        report = ChaosTraceReplay(
            trace, str(tmp_path), fail_at=2, fail_n=4,
            servicer_kw=dict(cfg=cfg),
        ).run()
        assert report.parity_ok, report.parity_detail
        assert report.events_replayed == len(trace.events)
        assert report.breaker_trips >= 1
