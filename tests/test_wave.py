"""solver/wave.py unit tests: the packed-key encode/decode and the
in-wave certification resolver, exercised directly (the full-cycle
bit-parity lives in tests/test_parity_fuzz.py / test_parallel.py — here
the primitives the shard_map wave path consumes are pinned in
isolation, so a refactor of that path cannot silently change the
math)."""

import jax.numpy as jnp
import numpy as np
import pytest

from koordinator_tpu.config import CycleConfig
from koordinator_tpu.model import resources as res
from koordinator_tpu.solver import wave as wv
from koordinator_tpu.solver.greedy import step_feasible_scores

R = res.NUM_RESOURCES


class TestPackedKeys:
    def test_roundtrip(self):
        rng = np.random.RandomState(0)
        N = 97
        scores = jnp.asarray(rng.randint(-5000, 5000, 64), jnp.int64)
        idx = jnp.asarray(rng.randint(0, N, 64), jnp.int64)
        keys = wv.pack_keys(scores, jnp.ones(64, bool), idx, N)
        got_s, got_i = wv.decode_key(keys, N)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(scores))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(idx))
        assert bool(wv.score_feasible(got_s).all())

    def test_infeasible_slots_decode_as_sentinel(self):
        N = 16
        keys = wv.pack_keys(
            jnp.asarray([100, 100], jnp.int64),
            jnp.asarray([True, False]),
            jnp.asarray([3, 3], jnp.int64),
            N,
        )
        s, i = wv.decode_key(keys, N)
        assert bool(wv.score_feasible(s[0]))
        assert not bool(wv.score_feasible(s[1]))
        assert int(i[1]) == 3  # the index term survives the sentinel
        assert int(keys[1]) <= int(wv.sentinel_threshold(N))
        assert int(keys[0]) > int(wv.sentinel_threshold(N))

    def test_ordering_is_score_desc_then_index_asc(self):
        N = 32
        feas = jnp.ones((), bool)

        def key(s, i):
            return int(
                wv.pack_keys(
                    jnp.int64(s), feas, jnp.int64(i), N
                )
            )

        assert key(10, 5) > key(9, 0)  # higher score wins
        assert key(10, 2) > key(10, 3)  # equal score: lower index wins
        assert key(0, 0) > key(-1, 0)
        # uniqueness: distinct (score, idx) -> distinct keys
        seen = {key(s, i) for s in range(-3, 4) for i in range(N)}
        assert len(seen) == 7 * N


def _cand(W, M, gid, alloc_rows, nreq_rows):
    """Candidate-row dict for resolve_wave's k_M path (zeros elsewhere)."""
    return dict(
        gid=jnp.asarray(gid, jnp.int64),
        alloc=jnp.asarray(alloc_rows, jnp.int64),
        nreq=jnp.asarray(nreq_rows, jnp.int64),
        nest=jnp.zeros((W, M, R), jnp.int64),
        usage=jnp.zeros((W, M, R), jnp.int64),
        ok=jnp.ones((W, M), bool),
        fresh=jnp.ones((W, M), bool),
        xval=jnp.zeros((W, M), jnp.int64),
        xfeas=jnp.ones((W, M), bool),
    )


def _frozen_keys(cand, cfg, n_total, preq, psreq, pest, qrt, qlim, quse):
    """Frozen per-pod candidate keys through the same step semantics the
    resolver re-keys with (what the wave paths capture before a round)."""
    rows = []
    for w in range(preq.shape[0]):
        feas, total = step_feasible_scores(
            cand["nreq"][w], cand["nest"][w], quse, cand["alloc"][w],
            cand["usage"][w], cand["fresh"][w], cand["ok"][w],
            preq[w], psreq[w], pest[w], jnp.int32(-1), jnp.bool_(True),
            qrt, qlim, cfg,
        )
        rows.append(wv.pack_keys(total, feas, cand["gid"][w], n_total))
    return jnp.stack(rows)


def _vec(cpu):
    v = np.zeros(R, np.int64)
    v[0] = cpu
    return v


class TestResolveWave:
    CFG = CycleConfig(enable_loadaware=False)

    def _quota_free(self):
        qrt = jnp.zeros((1, R), jnp.int64)
        qlim = jnp.zeros((1, R), bool)
        quse = jnp.zeros((1, R), jnp.int64)
        return qrt, qlim, quse

    def _resolve(self, cand_key, cand, preq, qids=None, wvalid=None,
                 quota=None):
        W = preq.shape[0]
        qrt, qlim, quse = quota if quota is not None else self._quota_free()
        return wv.resolve_wave(
            cand_key,
            cand=cand,
            universe=None,
            preq_wave=preq,
            pest_wave=jnp.zeros_like(preq),
            psreq_wave=preq,
            pqid_wave=(
                jnp.asarray(qids, jnp.int32)
                if qids is not None
                else jnp.full((W,), -1, jnp.int32)
            ),
            pvalid_wave=jnp.ones((W,), bool),
            pprod_wave=jnp.zeros((W,), bool),
            wvalid=(
                jnp.asarray(wvalid)
                if wvalid is not None
                else jnp.ones((W,), bool)
            ),
            qrt=qrt,
            qlim=qlim,
            quse=quse,
            cfg=self.CFG,
            n_total=4,
            prod_sensitive=False,
        )

    def test_consumed_candidate_ends_the_commit_prefix(self):
        """Two pods, both frozen onto the same one-pod-sized node: pod 0
        commits, pod 1's only candidate fills in-wave and its k_M is
        above the sentinel — it must END the prefix (feasible nodes
        below k_M may remain), never commit -1."""
        W, M = 2, 1
        cand = _cand(
            W, M,
            gid=[[0], [0]],
            alloc_rows=[[_vec(10)], [_vec(10)]],
            nreq_rows=np.zeros((W, M, R)),
        )
        preq = jnp.asarray([_vec(8), _vec(8)], jnp.int64)
        qrt, qlim, quse = self._quota_free()
        cand_key = _frozen_keys(
            cand, self.CFG, 4, preq, preq, jnp.zeros_like(preq),
            qrt, qlim, quse,
        )
        choices, committed, done, _, ncommit = self._resolve(
            cand_key, cand, preq
        )
        assert np.asarray(choices).tolist() == [0, -1]
        assert np.asarray(committed).tolist() == [True, False]
        assert np.asarray(done).tolist() == [True, False]
        assert int(ncommit) == 1

    def test_disjoint_candidates_commit_the_whole_wave(self):
        W, M = 2, 1
        cand = _cand(
            W, M,
            gid=[[0], [1]],
            alloc_rows=[[_vec(10)], [_vec(10)]],
            nreq_rows=np.zeros((W, M, R)),
        )
        preq = jnp.asarray([_vec(8), _vec(8)], jnp.int64)
        qrt, qlim, quse = self._quota_free()
        cand_key = _frozen_keys(
            cand, self.CFG, 4, preq, preq, jnp.zeros_like(preq),
            qrt, qlim, quse,
        )
        choices, committed, done, _, ncommit = self._resolve(
            cand_key, cand, preq
        )
        assert np.asarray(choices).tolist() == [0, 1]
        assert np.asarray(done).tolist() == [True, True]
        assert int(ncommit) == 2

    def test_quota_blocked_pod_commits_unschedulable_in_wave(self):
        """Quota admission is node-invariant: a blocked pod is an exact
        -1 commit (the prefix continues past it), including blocks
        created by an EARLIER in-wave commit on the same quota."""
        W, M = 2, 1
        cand = _cand(
            W, M,
            gid=[[0], [1]],
            alloc_rows=[[_vec(100)], [_vec(100)]],
            nreq_rows=np.zeros((W, M, R)),
        )
        preq = jnp.asarray([_vec(8), _vec(8)], jnp.int64)
        # quota runtime fits ONE pod's cpu; both pods share quota 0
        qrt = jnp.asarray([_vec(10)], jnp.int64)
        qlim = jnp.asarray([_vec(1) > 0], bool).reshape(1, R)
        quse = jnp.zeros((1, R), jnp.int64)
        cand_key = _frozen_keys(
            cand, self.CFG, 4, preq, preq, jnp.zeros_like(preq),
            qrt, qlim, quse,
        )
        choices, committed, done, quse_new, ncommit = self._resolve(
            cand_key, cand, preq, qids=[0, 0], quota=(qrt, qlim, quse)
        )
        assert np.asarray(choices).tolist() == [0, -1]
        assert np.asarray(done).tolist() == [True, True]  # both exact
        assert int(ncommit) == 2
        assert int(np.asarray(quse_new)[0, 0]) == 8  # one commit charged

    def test_padding_lane_commits_without_taking_a_node(self):
        W, M = 2, 1
        cand = _cand(
            W, M,
            gid=[[0], [1]],
            alloc_rows=[[_vec(100)], [_vec(100)]],
            nreq_rows=np.zeros((W, M, R)),
        )
        preq = jnp.asarray([_vec(8), _vec(8)], jnp.int64)
        qrt, qlim, quse = self._quota_free()
        cand_key = _frozen_keys(
            cand, self.CFG, 4, preq, preq, jnp.zeros_like(preq),
            qrt, qlim, quse,
        )
        choices, committed, done, _, ncommit = self._resolve(
            cand_key, cand, preq, wvalid=[True, False]
        )
        assert np.asarray(choices).tolist() == [0, -1]
        assert np.asarray(committed).tolist() == [True, False]
        assert int(ncommit) == 2

    def test_most_allocated_requires_the_universe(self):
        with pytest.raises(ValueError, match="universe"):
            wv.resolve_wave(
                jnp.zeros((1, 1), jnp.int64),
                cand=_cand(1, 1, [[0]], [[_vec(10)]], np.zeros((1, 1, R))),
                universe=None,
                preq_wave=jnp.zeros((1, R), jnp.int64),
                pest_wave=jnp.zeros((1, R), jnp.int64),
                psreq_wave=jnp.zeros((1, R), jnp.int64),
                pqid_wave=jnp.full((1,), -1, jnp.int32),
                pvalid_wave=jnp.ones((1,), bool),
                pprod_wave=jnp.zeros((1,), bool),
                wvalid=jnp.ones((1,), bool),
                qrt=jnp.zeros((1, R), jnp.int64),
                qlim=jnp.zeros((1, R), bool),
                quse=jnp.zeros((1, R), jnp.int64),
                cfg=CycleConfig(fit_scoring_strategy="MostAllocated"),
                n_total=4,
                prod_sensitive=False,
            )
