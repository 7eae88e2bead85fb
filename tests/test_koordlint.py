"""koordlint under tier-1: the full static pass must hold at HEAD, and
every rule must catch its seeded PR-1 regression class.

``TestRepoIsClean`` is the enforcement seam — a violation anywhere in
the repo fails ``pytest tests/`` with the same file:line report the
``python -m koordinator_tpu.analysis`` CLI prints, zero new CI infra.
The seeded-regression tests feed synthetic sources through the same
code path the CLI uses (``run_rules_on_source`` / the wire-contract
text functions), so a rule that silently stops firing fails here too.
"""

import os
import re
import shutil
import subprocess
import textwrap

import pytest

from koordinator_tpu.analysis import RULES, wire_contract
from koordinator_tpu.analysis.core import (
    find_repo_root,
    parse_suppressions,
    run_repo,
    run_rules_on_source,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src: str, rules=None):
    return run_rules_on_source("fixture.py", textwrap.dedent(src), rules)


def read(*parts: str) -> str:
    with open(os.path.join(REPO, *parts), "r", encoding="utf-8") as f:
        return f.read()


class TestRepoIsClean:
    def test_full_pass_reports_zero_violations(self):
        violations = run_repo(root=REPO)
        assert violations == [], "\n" + "\n".join(
            v.format() for v in violations
        )

    def test_cli_exits_zero_on_repo(self):
        from koordinator_tpu.analysis.__main__ import main

        assert main(["--root", REPO]) == 0
        assert main(["--list-rules"]) == 0
        assert main(["--rules", "no-such-rule"]) == 2

    def test_cli_default_root_is_package_location_not_cwd(self, tmp_path,
                                                          monkeypatch):
        from koordinator_tpu.analysis.__main__ import main

        monkeypatch.chdir(tmp_path)
        assert main([]) == 0  # resolves the repo from the package, not cwd

    def test_rule_registry_matches_runner(self):
        # every advertised rule can be selected individually (empty
        # source + a wire-free repo pass: proves selection wiring
        # without five more full-repo scans)
        for rule in RULES:
            run_rules_on_source("f.py", "", [rule])
        run_repo(root=REPO, rules=list(RULES), wire=False)


class TestDonationSafety:
    FIXTURE = """
    import jax
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def scatter(arr, idx, val):
        return arr.at[idx].set(val)

    def apply(buf, idx, val):
        out = scatter(buf, idx, val)
        check = buf.sum()
        return out, check
    """

    def test_read_after_donate_caught(self):
        got = lint(self.FIXTURE)
        assert [(v.rule, v.line) for v in got] == [("donation-safety", 11)]
        assert "donated to scatter()" in got[0].message

    def test_rebind_idiom_is_clean(self):
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def scatter(arr, idx, val):
            return arr.at[idx].set(val)

        def apply(buf, idx, val):
            buf = scatter(buf, idx, val)
            return buf.sum()
        """) == []

    def test_same_line_read_after_donate_caught(self):
        # the one-line form of the bug: the read sits on the call's line
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def scatter(arr, idx, val):
            return arr.at[idx].set(val)

        def apply(buf, idx, val):
            return scatter(buf, idx, val), buf.sum()
        """)
        assert [(v.rule, v.line) for v in got] == [("donation-safety", 10)]

    def test_read_before_donate_on_same_line_is_clean(self):
        # left-to-right evaluation: the read happens before the donation
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def scatter(arr, val):
            return arr + val

        def apply(buf, val):
            return buf.sum() + scatter(buf, val).sum()
        """) == []

    def test_augassign_is_a_read_not_a_forgiving_rebind(self):
        # `buf += 1` after donating buf READS the donated buffer — it is
        # a violation itself and must not silence the later read either
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def scatter(arr, idx, val):
            return arr.at[idx].set(val)

        def apply(buf, idx, val):
            out = scatter(buf, idx, val)
            buf += 1
            return out, buf
        """)
        assert [v.line for v in got] == [11, 12]
        assert all(v.rule == "donation-safety" for v in got)

    def test_jit_call_form_and_kwarg_donation(self):
        got = lint("""
        import jax

        def _inner(arr, val):
            return arr + val

        update = jax.jit(_inner, donate_argnums=(0,))

        def use(state, val):
            out = update(state, val)
            return out, state.mean()
        """)
        assert [(v.rule, v.line) for v in got] == [("donation-safety", 11)]

    def test_suppression_tag(self):
        src = self.FIXTURE.replace(
            "check = buf.sum()",
            "check = buf.sum()  # koordlint: disable=donation-safety(pre-donate copy held by caller)",
        )
        assert lint(src) == []

    def test_known_cross_module_donor_rescore_dirty_caught(self):
        # ISSUE 9: the resident-score-tensor scatter's jit wrapper lives
        # in solver/incremental.py, invisible to the module-local scan —
        # the known-donor table must still catch a read of the donated
        # scores buffer at a cross-module call site
        got = lint("""
        from koordinator_tpu.solver.incremental import rescore_dirty

        def advance(snap, scores, feasible, dirty, cfg):
            out_s, out_f = rescore_dirty(snap, scores, feasible, dirty, set(), cfg)
            stale = scores.sum()
            return out_s, out_f, stale
        """)
        assert [(v.rule, v.line) for v in got] == [("donation-safety", 6)]
        assert "donated to rescore_dirty()" in got[0].message

    def test_known_donor_rebind_and_non_donated_args_clean(self):
        # feasible is NOT donated (in-flight readbacks hold it): reading
        # it after the call is fine, and the rebind idiom forgives scores
        assert lint("""
        from koordinator_tpu.solver.incremental import rescore_dirty
        from koordinator_tpu.solver.resident import apply_flat_delta

        def advance(snap, scores, feasible, dirty, cfg):
            scores, feasible = rescore_dirty(
                snap, scores, feasible, dirty, set(), cfg)
            return scores, feasible.sum()

        def scatter(buf, idx, val):
            buf = apply_flat_delta(buf, idx, val)
            return buf
        """) == []

    def test_known_donor_apply_flat_delta_caught(self):
        got = lint("""
        from koordinator_tpu.solver.resident import apply_flat_delta

        def scatter(buf, idx, val):
            out = apply_flat_delta(buf, idx, val)
            return out, buf.sum()
        """)
        assert [(v.rule, v.line) for v in got] == [("donation-safety", 6)]

    def test_local_def_overrides_known_donor(self):
        # a module-LOCAL jitted def named rescore_dirty declares its own
        # (empty) donation contract; the cross-module table must not
        # impose the solver helper's on it
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def rescore_dirty(snapshot, scores, feasible, a, b, cfg):
            return scores, feasible

        def advance(snap, scores, feasible, cfg):
            out = rescore_dirty(snap, scores, feasible, 1, 2, cfg)
            return out, scores.sum()
        """) == []


class TestRetraceHazard:
    def test_tracer_branch_in_jitted_fixture(self):
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def cycle(x, n, cfg):
            if n > 3:
                x = x + 1
            assert n >= 0
            if cfg.flag:
                x = x * 2
            if x is not None:
                x = x - 1
            return x
        """)
        assert [(v.rule, v.line) for v in got] == [
            ("retrace-hazard", 7),
            ("retrace-hazard", 9),
        ]

    def test_shape_guards_are_clean(self):
        # shape/len branches are trace-time constants, not retraces
        assert lint("""
        import jax

        @jax.jit
        def f(x, y):
            if x.shape[0] > 3:
                x = x + 1
            assert len(x) == len(y)
            if x.ndim == 2 and x.size > 0:
                x = x * 2
            return x
        """) == []

    def test_static_and_is_none_branches_are_clean(self):
        # the repo's own idioms: branch on static cfg, on extras presence
        assert lint("""
        import jax, jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def cycle(x, extra, cfg):
            if cfg.enable:
                x = x + 1
            if extra is not None:
                x = x + extra
            return x
        """) == []

    def test_unhashable_and_str_tuple_static_args(self):
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg", "names"))
        def f(x, cfg, names):
            return x

        def call(x):
            a = f(x, cfg=[1, 2], names=None)
            b = f(x, cfg=None, names=("pod-a", "pod-b"))
            return a, b
        """)
        msgs = [v.message for v in got]
        assert any("unhashable" in m for m in msgs)
        assert any("tuple-of-str" in m for m in msgs)

    def test_traced_wave_knobs_caught(self):
        """A jit boundary taking wave/top_m traced is a silent per-cycle
        retrace (the width selects loop structure); both decorator and
        call-form jit spellings must be caught, and the static spelling
        must pass (rule shape 4)."""
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def cycle(snapshot, cfg, wave, top_m):
            return snapshot

        def _inner(snapshot, wave):
            return snapshot

        batched = jax.jit(_inner)
        """)
        msgs = [(v.rule, v.message) for v in got]
        assert len(msgs) == 3
        assert all(r == "retrace-hazard" for r, _ in msgs)
        assert sum("'wave'" in m for _, m in msgs) == 2
        assert sum("'top_m'" in m for _, m in msgs) == 1
        assert all("static_argnames" in m for _, m in msgs)

    def test_static_wave_knobs_are_clean(self):
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg", "wave", "top_m"))
        def cycle(snapshot, cfg, wave, top_m):
            return snapshot

        def _inner(snapshot, wave):
            return snapshot

        batched = jax.jit(_inner, static_argnames=("wave",))
        """) == []

    def test_traced_mesh_knobs_caught(self):
        """ISSUE 7: a jit boundary taking mesh/device-count/shard-width
        traced re-specializes the partitioned program per value — the
        same silent retrace class as the wave knobs; decorator and
        call-form spellings both caught."""
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def sharded_cycle(snapshot, cfg, mesh):
            return snapshot

        def _inner(arr, n_shards):
            return arr

        scatter = jax.jit(_inner)
        """)
        msgs = [(v.rule, v.message) for v in got]
        assert len(msgs) == 2
        assert all(r == "retrace-hazard" for r, _ in msgs)
        assert sum("'mesh'" in m for _, m in msgs) == 1
        assert sum("'n_shards'" in m for _, m in msgs) == 1
        assert all("static_argnames" in m for _, m in msgs)

    def test_static_mesh_knobs_are_clean(self):
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg", "mesh"))
        def sharded_cycle(snapshot, cfg, mesh):
            return snapshot

        def _inner(arr, n_shards):
            return arr

        scatter = jax.jit(_inner, static_argnames=("n_shards",))
        """) == []

    def test_traced_dirty_knobs_caught(self):
        """ISSUE 9: a jit boundary taking a dirty COUNT traced is the
        same silent retrace class — delta sizes vary per cycle, so the
        rescore would re-specialize per distinct count; the count must
        ride a bucket-padded index vector instead.  Decorator and
        call-form spellings both."""
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def rescore(snapshot, scores, cfg, n_dirty):
            return scores

        def _inner(scores, dirty_width):
            return scores

        column_rescore = jax.jit(_inner)
        """)
        msgs = [(v.line, v.message) for v in got]
        assert len(msgs) == 2, msgs
        assert sum("'n_dirty'" in m for _, m in msgs) == 1
        assert sum("'dirty_width'" in m for _, m in msgs) == 1
        assert all("pad" in m for _, m in msgs)

    def test_static_or_padded_dirty_params_are_clean(self):
        # a padded index VECTOR (node_idx/pod_idx) carries no count at
        # the boundary; an explicitly-static count is also accepted
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def rescore(snapshot, scores, node_idx, pod_idx, cfg):
            return scores

        def _inner(scores, n_dirty):
            return scores

        sized = jax.jit(_inner, static_argnames=("n_dirty",))
        """) == []

    def test_traced_candidate_knobs_caught(self):
        """ISSUE 16: a jit boundary taking a candidate width/count
        traced is the same silent retrace class — the width is
        configuration (it rides the static CycleConfig) and per-pod
        feasible counts vary with every delta, so each distinct value
        re-specializes the sparse [P, C] program; decorator and
        call-form spellings both caught."""
        got = lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def sparse_score(snapshot, cand, cfg, num_candidates):
            return cand

        def _inner(cand, c_width):
            return cand

        gather = jax.jit(_inner)
        """)
        msgs = [(v.rule, v.message) for v in got]
        assert len(msgs) == 2, msgs
        assert all(r == "retrace-hazard" for r, _ in msgs)
        assert sum("'num_candidates'" in m for _, m in msgs) == 1
        assert sum("'c_width'" in m for _, m in msgs) == 1
        assert all(
            "pad the candidate list, don't trace the count" in m
            for _, m in msgs
        )

    def test_static_or_padded_candidate_params_are_clean(self):
        # the shipped spelling: C rides the static cfg and the list is
        # padded to C with sentinels — no count at any boundary; an
        # explicitly-static width is also accepted
        assert lint("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def sparse_score(snapshot, cand, count, cfg):
            return cand

        def _inner(cand, candidate_width):
            return cand

        sized = jax.jit(_inner, static_argnames=("candidate_width",))
        """) == []

    def test_mesh_knob_in_shard_map_body_caught(self):
        """A shard_map body taking a mesh knob as a PARAMETER receives
        it as a traced per-shard operand; the mesh belongs in the
        shard_map(..., mesh=) binding or the closure."""
        got = lint("""
        import jax
        from jax.sharding import PartitionSpec as P

        def run(arr, mesh):
            def body(a, num_shards):
                return a * num_shards

            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("nodes"), P()),
                out_specs=P("nodes"),
            )(arr, mesh.size)
        """)
        assert len(got) == 1
        assert got[0].rule == "retrace-hazard"
        assert "'num_shards'" in got[0].message
        assert "shard_map" in got[0].message

    def test_shard_map_body_without_mesh_knobs_is_clean(self):
        assert lint("""
        import jax
        from jax.sharding import PartitionSpec as P

        def run(arr, mesh):
            def body(a, deltas):
                return a + deltas

            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("nodes"), P()),
                out_specs=P("nodes"),
            )(arr, arr)
        """) == []

    def test_shard_map_body_resolution_is_lexically_scoped(self):
        """Two same-named nested defs in different functions must not
        cross-resolve: the clean shard_map body in run() resolves to
        run's own `body`, never to the unrelated `body(a, num_shards)`
        elsewhere in the file (a file-wide name table collided here)."""
        assert lint("""
        import jax
        from jax.sharding import PartitionSpec as P

        def run(arr, mesh):
            def body(a, deltas):
                return a + deltas

            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("nodes"), P()),
                out_specs=P("nodes"),
            )(arr, arr)

        def unrelated():
            def body(a, num_shards):
                return a * num_shards

            return body
        """) == []

    def test_namey_pytree_metadata(self):
        got = lint("""
        import dataclasses
        import jax

        @dataclasses.dataclass
        class T:
            rows: object
            names: tuple = ()

        jax.tree_util.register_dataclass(
            T, data_fields=["rows"], meta_fields=["names"]
        )
        """)
        assert len(got) == 1 and "PR-1" in got[0].message


class TestTermConfigRule:
    """retrace-hazard shape 5 (ISSUE 15): CycleConfig term configs must
    be frozen/hashable, mappings through _freeze."""

    CLEAN = """
    import dataclasses

    def _freeze(m):
        return tuple(sorted(m.items())) if not isinstance(m, tuple) else m

    @dataclasses.dataclass(frozen=True)
    class PackingTermArgs:
        weight: int = 1
        headroom: ResMap = ()

        def __post_init__(self):
            object.__setattr__(self, "headroom", _freeze(self.headroom))

    @dataclasses.dataclass(frozen=True)
    class CycleConfig:
        packing: "PackingTermArgs | None" = None
        wave: int = 1
    """

    def test_compliant_term_config_is_clean(self):
        assert lint(self.CLEAN, rules=["retrace-hazard"]) == []

    def test_unfrozen_term_config_flagged(self):
        got = lint("""
        import dataclasses

        @dataclasses.dataclass
        class SensitivityTermArgs:
            weight: int = 1

        @dataclasses.dataclass(frozen=True)
        class CycleConfig:
            sensitivity: "SensitivityTermArgs | None" = None
        """, rules=["retrace-hazard"])
        assert [v.rule for v in got] == ["retrace-hazard"]
        assert "frozen=True" in got[0].message

    def test_unfrozen_mapping_field_flagged(self):
        got = lint("""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class PackingTermArgs:
            headroom: ResMap = ()

        @dataclasses.dataclass(frozen=True)
        class CycleConfig:
            packing: "PackingTermArgs | None" = None
        """, rules=["retrace-hazard"])
        assert len(got) == 1
        assert "_freeze" in got[0].message

    def test_mutable_default_flagged(self):
        got = lint("""
        import dataclasses

        def _freeze(m):
            return tuple(m)

        @dataclasses.dataclass(frozen=True)
        class HetTermArgs:
            table: list = []

        @dataclasses.dataclass(frozen=True)
        class CycleConfig:
            heterogeneity: "HetTermArgs | None" = None
        """, rules=["retrace-hazard"])
        assert any("mutable" in v.message for v in got)

    def test_transitive_reach_through_nested_config(self):
        # a mapping two hops from CycleConfig is still checked
        got = lint("""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class AggArgs:
            thresholds: ResMap = ()

        @dataclasses.dataclass(frozen=True)
        class LoadArgs:
            aggregated: "AggArgs | None" = None

        @dataclasses.dataclass(frozen=True)
        class CycleConfig:
            loadaware: LoadArgs = LoadArgs()
        """, rules=["retrace-hazard"])
        assert len(got) == 1 and "AggArgs.thresholds" in got[0].message

    def test_no_cycleconfig_means_no_checks(self):
        assert lint("""
        import dataclasses

        @dataclasses.dataclass
        class Whatever:
            stuff: dict = None
        """, rules=["retrace-hazard"]) == []


class TestHostSyncInJit:
    def test_all_four_sync_shapes(self):
        got = lint("""
        import jax, numpy as np, jax.numpy as jnp

        @jax.jit
        def f(x):
            y = np.asarray(x)
            z = x.item()
            w = int(jnp.max(x))
            print("debug", x)
            return y, z, w
        """)
        assert [v.line for v in got] == [6, 7, 8, 9]
        assert all(v.rule == "host-sync-in-jit" for v in got)

    def test_closure_scanned_once_nested_jit_not_double_reported(self):
        # a lax.scan step closure executes under the enclosing trace and
        # is scanned; a nested JITTED def is reported exactly once
        got = lint("""
        import jax, numpy as np

        @jax.jit
        def outer(x):
            def step(carry, v):
                bad = np.asarray(v)
                return carry, bad
            return jax.lax.scan(step, x, x)

        @jax.jit
        def parent(x):
            @jax.jit
            def inner(y):
                return np.asarray(y)
            return inner(x)
        """)
        assert [v.line for v in got] == [7, 15]  # once each, no doubles
        assert "outer" in got[0].message  # closure attributed to outer
        assert "inner" in got[1].message  # nested jit attributed to itself

    def test_host_side_int_is_clean(self):
        # int() on shapes/enums is a trace-time constant, not a sync
        assert lint("""
        import jax, jax.numpy as jnp

        @jax.jit
        def f(x, k):
            n = int(x.shape[0])
            m = int(SomeEnum.PROD)
            return x[:n] + m
        """) == []


class TestSpanLeak:
    """ISSUE 4: the obs span API must end every span on every exit."""

    def test_naked_begin_span_caught(self):
        got = lint("""
        def stage(recorder):
            h = recorder.begin_span("dispatch")
            do_work()
            recorder.end_span(h)
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 3)]
        assert "try/finally" in got[0].message

    def test_try_finally_form_is_clean(self):
        assert lint("""
        def stage(recorder):
            h = recorder.begin_span("dispatch")
            try:
                do_work()
            finally:
                recorder.end_span(h)
        """) == []

    def test_begin_inside_protected_try_is_clean(self):
        assert lint("""
        def stage(recorder):
            try:
                h = recorder.begin_span("x")
                work()
            finally:
                recorder.end_span(h)
        """) == []

    def test_begin_in_finally_is_not_protected_by_itself(self):
        # a begin inside the very finalbody that ends OTHER spans has no
        # guarantee of its own
        got = lint("""
        def stage(recorder):
            try:
                work()
            finally:
                h = recorder.begin_span("cleanup")
                recorder.end_span(other)
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 6)]

    def test_try_without_end_in_finally_caught(self):
        got = lint("""
        def stage(recorder):
            h = recorder.begin_span("dispatch")
            try:
                do_work()
            finally:
                log("done")
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 3)]

    def test_context_manager_implementation_is_clean(self):
        # obs/spans.py's own _SpanContext shape: begin in __enter__,
        # end in __exit__
        assert lint("""
        class Ctx:
            def __enter__(self):
                self.h = self.rec.begin_span(self.name)
                return self

            def __exit__(self, *exc):
                self.rec.end_span(self.h)
        """) == []

    def test_enter_without_matching_exit_caught(self):
        got = lint("""
        class Ctx:
            def __enter__(self):
                self.h = self.rec.begin_span(self.name)
                return self

            def __exit__(self, *exc):
                pass
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 4)]

    def test_suppression_tag(self):
        assert lint("""
        def stage(recorder):
            h = recorder.begin_span("dispatch")  # koordlint: disable=span-leak(caller owns the end)
            return h
        """) == []


class TestSpanLeakTraceAPI:
    """ISSUE 14: the rule extends over the distributed-tracing surface
    — a ``start_trace_span`` must end/abort on all exits (a leaked
    TraceSpan never exports, so the assembled tree silently loses the
    RPC) and a ``SpanExporter`` handle must be closed."""

    def test_leaked_trace_span_caught(self):
        got = lint("""
        def rpc(spans, trace_id):
            span = spans.start_trace_span("score", trace_id)
            reply = serve()
            span.end()
            return reply
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 3)]
        assert "TraceSpan" in got[0].message

    def test_abort_in_handler_plus_end_is_clean(self):
        # the server wrapper shape (bridge/server.py sync/score/assign)
        assert lint("""
        def rpc(spans, trace_id):
            span = spans.start_trace_span("score", trace_id)
            try:
                reply = serve()
            except BaseException as exc:
                span.abort(exc)
                raise
            span.end()
            return reply
        """) == []

    def test_end_in_finally_is_clean(self):
        assert lint("""
        def rpc(spans, trace_id):
            span = spans.start_trace_span("score", trace_id)
            try:
                return serve()
            finally:
                span.end()
        """) == []

    def test_with_block_is_clean(self):
        assert lint("""
        def rpc(spans, trace_id):
            with spans.start_trace_span("score", trace_id) as span:
                return serve(span)
        """) == []

    def test_factory_return_is_clean(self):
        # ScorerServicer._start_rpc_span: ownership moves to the caller
        assert lint("""
        def _start_rpc_span(self, name, req):
            return self.spans.start_trace_span(name, req.trace_id)
        """) == []

    def test_end_without_error_path_caught(self):
        # an end() with no abort/finally anywhere: the exception path
        # leaks the span
        got = lint("""
        def rpc(spans, trace_id):
            span = spans.start_trace_span("score", trace_id)
            reply = serve()
            if reply.ok:
                span.end()
            return reply
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 3)]

    def test_unclosed_exporter_caught(self):
        got = lint("""
        def export_all(records, path):
            exporter = SpanExporter(path)
            for record in records:
                exporter.export(record)
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 3)]
        assert "closed" in got[0].message

    def test_exporter_with_block_is_clean(self):
        assert lint("""
        def export_all(records, path):
            with SpanExporter(path) as exporter:
                for record in records:
                    exporter.export(record)
        """) == []

    def test_exporter_close_in_finally_is_clean(self):
        assert lint("""
        def export_all(records, path):
            exporter = SpanExporter(path)
            try:
                for record in records:
                    exporter.export(record)
            finally:
                exporter.close()
        """) == []

    def test_exporter_held_on_self_with_close_is_clean(self):
        # the CycleTelemetry / ScorerClient lifetime shape
        assert lint("""
        class Telemetry:
            def __init__(self, path):
                self.exporter = SpanExporter(path)

            def close(self):
                self.exporter.close()
        """) == []

    def test_exporter_on_self_without_close_method_caught(self):
        got = lint("""
        class Telemetry:
            def __init__(self, path):
                self.exporter = SpanExporter(path)
        """)
        assert [(v.rule, v.line) for v in got] == [("span-leak", 4)]

    def test_trace_span_suppression_tag(self):
        assert lint("""
        def launch(spans, trace_id):
            span = spans.start_trace_span("launch", trace_id)  # koordlint: disable=span-leak(ends in the readback closure)
            return span
        """) == []


class TestHostSyncObsAPI:
    """The obs API inside jitted code is the print() trap plus a
    potential tracer concretization — the host-sync rule covers it."""

    def test_obs_calls_in_jit_caught(self):
        got = lint("""
        import jax

        @jax.jit
        def cycle(x, spans):
            h = spans.begin_span("inner")
            try:
                y = x + 1
            finally:
                spans.end_span(h)
            with spans.span("scale"):
                y = y * 2
            spans.note("rounds", y)
            return y
        """)
        assert [v.line for v in got] == [6, 10, 11, 13]
        assert all(v.rule == "host-sync-in-jit" for v in got)
        assert all("obs span API" in v.message for v in got)

    def test_telemetry_receiver_chain_caught(self):
        got = lint("""
        import jax

        @jax.jit
        def cycle(x, self):
            self.telemetry.spans.note("path", "scan")
            return x
        """)
        assert [(v.rule, v.line) for v in got] == [("host-sync-in-jit", 6)]

    def test_profiler_annotation_inside_jit_caught(self):
        got = lint("""
        import jax
        from koordinator_tpu.obs import spans

        @jax.jit
        def cycle(x):
            with spans.profiled("dispatch"):
                y = x + 1
            with profiled("readback") as ann:
                tag_cycle(ann, x)
            return y
        """)
        assert [(v.rule, v.line) for v in got] == [
            ("host-sync-in-jit", 7), ("host-sync-in-jit", 9),
            ("host-sync-in-jit", 10),
        ]

    def test_obs_outside_jit_is_clean(self):
        assert lint("""
        def serve(recorder, snap):
            with recorder.span("dispatch"):
                result = run_cycle(snap)
            recorder.note("path", result.path)
            return result
        """) == []

    def test_unrelated_span_named_method_is_clean(self):
        # .span()/.note() only count on a telemetry-ish receiver
        assert lint("""
        import jax

        @jax.jit
        def f(x, tree):
            y = tree.span(x)
            tree.note(y)
            return y
        """) == []


class TestLockHeldDispatch:
    """ISSUE 5: the serialized-daemon bug class — a blocking device
    readback performed while the servicer state lock is held serializes
    every RPC behind one transfer.  The coalescing refactor's invariant
    is lexical (capture under the lock, read back outside), so the rule
    checks exactly that."""

    def test_readbacks_under_state_lock_caught(self):
        out = lint("""
        import numpy as np
        import jax

        class Servicer:
            def score(self):
                with self._state_lock:
                    snap = self.state.snapshot()
                    scores = np.asarray(snap.scores)
                    n = snap.rounds.item()
                    snap.result.block_until_ready()
                    a, b = jax.device_get((snap.a, snap.b))
                return scores
        """, ["lock-held-dispatch"])
        assert len(out) == 4
        assert all(v.rule == "lock-held-dispatch" for v in out)

    def test_pre_split_servicer_spelling_caught(self):
        # the pre-refactor servicer held a bare self._lock across the
        # readback; the rule must catch that spelling too
        out = lint("""
        import numpy as np

        class Servicer:
            def assign(self):
                with self._lock:
                    assignment = np.asarray(self.result.assignment)
                return assignment
        """, ["lock-held-dispatch"])
        assert [v.line for v in out] == [7]

    def test_capture_then_readback_outside_is_clean(self):
        out = lint("""
        import numpy as np
        import jax

        class Servicer:
            def score(self):
                with self._state_lock:
                    snap = self.state.snapshot()
                    sid = self.snapshot_id()
                scores = np.asarray(snap.scores)
                a, b = jax.device_get((snap.a, snap.b))
                return sid, scores
        """, ["lock-held-dispatch"])
        assert out == []

    def test_closure_defined_under_lock_is_clean(self):
        # a closure DEFINED under the lock runs elsewhere (the device
        # section hands it to the dispatch queue) — not a violation
        out = lint("""
        import numpy as np

        class Servicer:
            def assign(self):
                with self._state_lock:
                    def launch():
                        return np.asarray(self.result.assignment)
                return self.dispatch.run_exclusive(launch)
        """, ["lock-held-dispatch"])
        assert out == []

    def test_unrelated_lock_names_are_clean(self):
        out = lint("""
        import numpy as np

        def save(self):
            with self._PALLAS_LOCK:
                return np.asarray(self.table)
        """, ["lock-held-dispatch"])
        assert out == []

    def test_suppression_tag(self):
        out = lint("""
        import numpy as np

        def save(self):
            with self._lock:
                return np.array(self.index)  # koordlint: disable=lock-held-dispatch
        """, ["lock-held-dispatch"])
        assert out == []

    # -- ISSUE 6: the pipeline seam (launch sections must not block) --
    def test_blocking_readback_in_launch_section_caught(self):
        out = lint("""
        import jax
        from koordinator_tpu.bridge.coalesce import launch_section

        class Servicer:
            @launch_section
            def _score_launch_batch(self, batch):
                scores = self.compute(batch)
                a, b = jax.device_get((scores.a, scores.b))
                scores.result.block_until_ready()
                return None
        """, ["lock-held-dispatch"])
        assert len(out) == 2
        assert all("launch critical section" in v.message for v in out)

    def test_attribute_decorator_form_caught(self):
        out = lint("""
        import numpy as np
        from koordinator_tpu.bridge import coalesce

        @coalesce.launch_section
        def launch(snap):
            return np.asarray(snap.scores)
        """, ["lock-held-dispatch"])
        assert [v.line for v in out] == [7]

    def test_readback_closure_inside_launch_section_is_clean(self):
        # the nested def IS the readback closure — the only code
        # allowed to block, run by the dispatcher off the launch lock
        out = lint("""
        import jax
        from koordinator_tpu.bridge.coalesce import launch_section

        class Servicer:
            @launch_section
            def _score_launch_batch(self, batch):
                scores = self.compute(batch)

                def _readback():
                    return jax.device_get(scores)

                return _readback
        """, ["lock-held-dispatch"])
        assert out == []

    def test_launch_lock_with_block_caught(self):
        out = lint("""
        import numpy as np

        class Dispatcher:
            def lead(self):
                with self._launch_lock:
                    return np.asarray(self.pending)
        """, ["lock-held-dispatch"])
        assert [v.line for v in out] == [7]
        assert "launch critical section" in out[0].message

    def test_undecorated_launch_helper_not_flagged(self):
        # lexical rule: only the decorator (or the lock) marks launch
        # code; a plain helper named "launch" stays out of scope
        out = lint("""
        import numpy as np

        def launch(snap):
            return np.asarray(snap.scores)
        """, ["lock-held-dispatch"])
        assert out == []


class TestBareRetry:
    """ISSUE 11: retry loops with a fixed ``time.sleep`` and no
    jitter/cap/deadline fail lint; computed (policy-paced) delays and
    poll loops without failure-eating handlers stay clean."""

    def test_fixed_sleep_retry_loop_caught(self):
        out = lint(
            """
            import time

            def redial(path):
                while True:
                    try:
                        connect(path)
                        return
                    except OSError:
                        pass
                    time.sleep(0.05)
            """,
            ["bare-retry"],
        )
        assert len(out) == 1
        assert out[0].rule == "bare-retry"
        assert "BackoffPolicy" in out[0].message

    def test_bare_sleep_import_form_caught(self):
        out = lint(
            """
            from time import sleep

            def poll():
                for attempt in range(5):
                    try:
                        return fetch()
                    except ValueError:
                        sleep(2)
            """,
            ["bare-retry"],
        )
        assert len(out) == 1

    def test_policy_paced_delay_is_clean(self):
        out = lint(
            """
            import time

            def redial(path, backoff):
                attempt = 0
                while True:
                    try:
                        connect(path)
                        return
                    except OSError:
                        pass
                    time.sleep(backoff.delay_ms(attempt) / 1000.0)
                    attempt += 1
            """,
            ["bare-retry"],
        )
        assert out == []

    def test_poll_loop_without_except_is_clean(self):
        # a liveness/status poll retries nothing — no handler in the
        # loop, no violation (bench's ppid watch is this shape)
        out = lint(
            """
            import time

            def watch(ppid):
                while alive(ppid):
                    time.sleep(0.5)
            """,
            ["bare-retry"],
        )
        assert out == []

    def test_except_outside_loop_is_clean(self):
        out = lint(
            """
            import time

            def watch(ppid):
                try:
                    while alive(ppid):
                        time.sleep(0.5)
                except KeyboardInterrupt:
                    pass
            """,
            ["bare-retry"],
        )
        assert out == []

    def test_nested_loops_report_once(self):
        out = lint(
            """
            import time

            def drain(items):
                while True:
                    for it in items:
                        try:
                            push(it)
                        except OSError:
                            time.sleep(1)
            """,
            ["bare-retry"],
        )
        assert len(out) == 1

    def test_suppression_tag(self):
        out = lint(
            """
            import time

            def watch(path):
                while True:
                    try:
                        check(path)
                    except OSError:
                        pass
                    time.sleep(0.5)  # koordlint: disable=bare-retry(fixed-cadence status poll, not a retry)
            """,
            ["bare-retry"],
        )
        assert out == []


class TestBroadExcept:
    def test_silent_swallow_caught_and_tag_respected(self):
        got = lint("""
        def g():
            try:
                risky()
            except Exception:
                pass
        """)
        assert [(v.rule, v.line) for v in got] == [("broad-except", 5)]
        assert lint("""
        def g():
            try:
                risky()
            except Exception:  # koordlint: disable=broad-except(probe may be down)
                pass
        """) == []

    def test_surfacing_handlers_pass(self):
        assert lint("""
        import logging

        def g():
            try:
                risky()
            except Exception:
                raise
            try:
                risky()
            except Exception:
                logging.getLogger(__name__).exception("boom")
            try:
                risky()
            except Exception as exc:
                return {"error": str(exc)}
        """) == []


class TestMetricsDocDrift:
    """ISSUE 12: the koord_scorer_* family table in OBSERVABILITY.md is
    the operator contract — one-sided drift against the families
    registered in obs/scorer_metrics.py must fail lint in BOTH
    directions, kinds included (the wire-contract shape applied to
    observability)."""

    PY_FIXTURE = textwrap.dedent('''
        CYCLE_LATENCY = "koord_scorer_cycle_latency_ms"
        SHED_TOTAL = "koord_scorer_shed_total"

        _FAMILIES = (
            (CYCLE_LATENCY, "histogram", "cycle latency"),
            (SHED_TOTAL, "counter", "shed reads"),
            ("koord_scorer_replica_lag_ms", "gauge", "inline literal"),
        )
    ''')
    MD_FIXTURE = textwrap.dedent("""
        # Observability

        | family | kind | labels | meaning |
        |---|---|---|---|
        | `koord_scorer_cycle_latency_ms` | histogram | `path` | latency |
        | `koord_scorer_shed_total` | counter | `method` | shed reads |
        | `koord_scorer_replica_lag_ms` | gauge | — | follower lag |
    """)

    def test_aligned_sources_are_clean(self):
        from koordinator_tpu.analysis import metricsdoc

        assert metricsdoc.diff_metrics_doc(
            self.PY_FIXTURE, self.MD_FIXTURE
        ) == []

    def test_head_is_clean(self):
        from koordinator_tpu.analysis import metricsdoc

        root = find_repo_root(REPO)
        assert metricsdoc.check_repo(root) == []

    def test_registered_but_undocumented_caught(self):
        from koordinator_tpu.analysis import metricsdoc

        bad_md = self.MD_FIXTURE.replace(
            "| `koord_scorer_shed_total` | counter | `method` | shed reads |\n",
            "",
        )
        got = metricsdoc.diff_metrics_doc(self.PY_FIXTURE, bad_md)
        assert len(got) == 1
        assert got[0].rule == "metrics-doc-drift"
        assert "koord_scorer_shed_total" in got[0].message
        assert "missing" in got[0].message
        # flags the _FAMILIES entry's line in the PY source
        assert got[0].path.endswith("scorer_metrics.py")
        assert got[0].line > 0

    def test_documented_but_unregistered_caught(self):
        from koordinator_tpu.analysis import metricsdoc

        bad_py = self.PY_FIXTURE.replace(
            '    (SHED_TOTAL, "counter", "shed reads"),\n', ""
        )
        got = metricsdoc.diff_metrics_doc(bad_py, self.MD_FIXTURE)
        assert len(got) == 1
        assert "never registered" in got[0].message
        # flags the doc row's line
        assert got[0].path.endswith("OBSERVABILITY.md")
        assert got[0].line > 0

    def test_kind_mismatch_caught(self):
        from koordinator_tpu.analysis import metricsdoc

        bad_md = self.MD_FIXTURE.replace(
            "| `koord_scorer_replica_lag_ms` | gauge |",
            "| `koord_scorer_replica_lag_ms` | counter |",
        )
        got = metricsdoc.diff_metrics_doc(self.PY_FIXTURE, bad_md)
        assert any(
            "documented as 'counter'" in v.message
            and "registered as 'gauge'" in v.message
            for v in got
        )

    def test_unknown_documented_kind_caught(self):
        from koordinator_tpu.analysis import metricsdoc

        bad_md = self.MD_FIXTURE.replace(
            "| `koord_scorer_shed_total` | counter |",
            "| `koord_scorer_shed_total` | summary |",
        )
        got = metricsdoc.diff_metrics_doc(self.PY_FIXTURE, bad_md)
        assert any("unknown kind 'summary'" in v.message for v in got)

    def test_vanished_tables_fail_loudly(self):
        from koordinator_tpu.analysis import metricsdoc

        # a refactor that moves either table must update the parser,
        # not silently disable the rule
        got = metricsdoc.diff_metrics_doc("X = 1\n", self.MD_FIXTURE)
        assert any("no _FAMILIES entries" in v.message for v in got)
        got = metricsdoc.diff_metrics_doc(self.PY_FIXTURE, "# no table\n")
        assert any("no koord_scorer_* rows" in v.message for v in got)

    def test_rule_is_registered_and_runs_in_run_repo(self):
        assert "metrics-doc-drift" in RULES
        # rules-filtered run executes only this rule and stays clean
        assert run_repo(root=REPO, rules=["metrics-doc-drift"]) == []


class TestWireContract:
    """Seeded one-sided edits to a wire.go fixture must each fail."""

    @pytest.fixture(scope="class")
    def sources(self):
        return {
            "proto": read("koordinator_tpu", "bridge", "scorer.proto"),
            "wire": read("go", "scorerclient", "wire.go"),
            "delta": read("go", "scorerclient", "delta.go"),
            "state": read("koordinator_tpu", "bridge", "state.py"),
            "codec": read("koordinator_tpu", "replication", "codec.py"),
            "wirecheck": read("koordinator_tpu", "bridge",
                              "wirecheck.py"),
            "replica": read("go", "scorerclient", "replica.go"),
        }

    def test_head_is_clean(self, sources):
        assert wire_contract.diff_proto_go(sources["proto"], sources["wire"]) == []
        assert wire_contract.check_delta_constants(
            sources["delta"], sources["state"]
        ) == []
        assert wire_contract.check_pb2_descriptor(sources["proto"]) == []

    def _edit(self, text, old, new):
        assert old in text
        return text.replace(old, new)

    def test_reordered_field_caught(self, sources):
        bad = self._edit(
            sources["wire"],
            "\tb = appendPackedInt64(b, 4, p.Priority)\n"
            "\tb = appendPackedInt32(b, 5, p.GangID)",
            "\tb = appendPackedInt32(b, 5, p.GangID)\n"
            "\tb = appendPackedInt64(b, 4, p.Priority)",
        )
        got = wire_contract.diff_proto_go(sources["proto"], bad)
        assert any("ascending" in v.message for v in got)

    def test_renumbered_field_caught(self, sources):
        bad = self._edit(
            sources["wire"],
            "b = appendPackedInt32(b, 5, p.GangID)",
            "b = appendPackedInt32(b, 6, p.GangID)",
        )
        msgs = [v.message for v in
                wire_contract.diff_proto_go(sources["proto"], bad)]
        assert any("proto field 6 is 'quota_id'" in m for m in msgs)
        assert any("never emits proto field 5" in m for m in msgs)

    def test_wrong_width_caught(self, sources):
        bad = self._edit(
            sources["wire"],
            "b = appendPackedInt64(b, 4, p.Priority)",
            "b = appendPackedInt32(b, 4, p.Priority)",
        )
        got = wire_contract.diff_proto_go(sources["proto"], bad)
        assert any("expects appendPackedInt64" in v.message for v in got)

    def test_wrong_endianness_width_caught(self, sources):
        bad = self._edit(
            sources["wire"],
            "r.Flat.Score = leInt64s(g.val)",
            "r.Flat.Score = leInt32s(g.val)",
        )
        got = wire_contract.diff_proto_go(sources["proto"], bad)
        assert any("i64 LE" in v.message for v in got)

    def test_guard_does_not_leak_to_later_local_emits(self):
        """A consumed/closed `if r.X {` guard must not be attributed to a
        later emit staged through a local variable."""
        from koordinator_tpu.analysis.wire_contract import parse_go_marshals

        src = (
            "func (r *Msg) Marshal() []byte {\n"
            "\tvar b []byte\n"
            "\tif r.Flat {\n"
            "\t\tb = appendVarintField(b, 3, 1)\n"
            "\t}\n"
            "\tstaged := r.Payload\n"
            "\tb = appendBytesField(b, 4, staged)\n"
            "\treturn b\n"
            "}\n"
        )
        emits = parse_go_marshals(src)["Msg"]
        assert [(e.num, e.field) for e in emits] == [(3, "Flat"), (4, None)]

    def test_dropped_reply_field_caught(self, sources):
        bad = self._edit(
            sources["wire"],
            "\t\tcase 2:\n\t\t\tr.Nodes = int64(f.u)\n",
            "",
        )
        got = wire_contract.diff_proto_go(sources["proto"], bad)
        assert any(
            "UnmarshalSyncReply never decodes proto field 2" in v.message
            for v in got
        )

    def test_delta_ratio_drift_caught(self, sources):
        bad = self._edit(
            sources["delta"],
            "DefaultMaxDeltaRatio = 0.25",
            "DefaultMaxDeltaRatio = 0.5",
        )
        got = wire_contract.check_delta_constants(bad, sources["state"])
        assert any("disagree" in v.message for v in got)

    def test_delta_endianness_helper_caught(self, sources):
        bad = self._edit(
            sources["delta"],
            "t.DeltaIdx = LEInt64Bytes(idx)",
            "t.DeltaIdx = beInt64Bytes(idx)",
        )
        got = wire_contract.check_delta_constants(bad, sources["state"])
        assert any("DeltaIdx" in v.message for v in got)

    def test_go_line_suppression_honored(self, sources, tmp_path):
        """A reasoned deviation in wire.go is suppressible with a Go
        comment on the flagged line, through the same run_repo filter
        tier-1 uses."""
        import shutil

        from koordinator_tpu.analysis.core import run_repo

        root = tmp_path / "repo"
        (root / "koordinator_tpu" / "bridge").mkdir(parents=True)
        (root / "go" / "scorerclient").mkdir(parents=True)
        shutil.copy(
            os.path.join(REPO, "koordinator_tpu", "bridge", "scorer.proto"),
            root / "koordinator_tpu" / "bridge" / "scorer.proto",
        )
        bad = self._edit(
            sources["wire"],
            "b = appendPackedInt64(b, 4, p.Priority)",
            "b = appendPackedInt32(b, 4, p.Priority)",
        )
        (root / "go" / "scorerclient" / "wire.go").write_text(bad)
        got = run_repo(root=str(root), rules=["wire-contract"])
        assert any("appendPackedInt64" in v.message for v in got)
        tagged = bad.replace(
            "b = appendPackedInt32(b, 4, p.Priority)",
            "b = appendPackedInt32(b, 4, p.Priority) "
            "// koordlint: disable=wire-contract(fixture)",
        )
        (root / "go" / "scorerclient" / "wire.go").write_text(tagged)
        got = run_repo(root=str(root), rules=["wire-contract"])
        assert not any("appendPackedInt64" in v.message for v in got)

    # -- replication stream framing (ISSUE 8): the three statements of
    #    the frame header must agree, and every seeded one-sided edit
    #    must fail lint, scorer.proto-style --
    def test_replication_framing_head_is_clean(self, sources):
        assert wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], sources["replica"]
        ) == []

    def test_replication_go_width_drift_caught(self, sources):
        bad = sources["replica"].replace(
            '{"generation", 8},', '{"generation", 4},'
        )
        assert bad != sources["replica"]
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], bad
        )
        assert any("replicaFrameFields" in v.message for v in got)

    def test_replication_go_field_order_drift_caught(self, sources):
        bad = sources["replica"].replace(
            '{"epoch", 8},\n\t{"generation", 8},',
            '{"generation", 8},\n\t{"epoch", 8},',
        )
        assert bad != sources["replica"]
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], bad
        )
        assert any("disagrees" in v.message for v in got)

    def test_replication_magic_and_version_drift_caught(self, sources):
        bad = sources["replica"].replace(
            "ReplicaFrameMagic   = 0x4B52504C",
            "ReplicaFrameMagic   = 0x4B52504D",
        )
        assert bad != sources["replica"]
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], bad
        )
        assert any("MAGIC" in v.message for v in got)
        bad = sources["replica"].replace(
            "ReplicaFrameVersion = 1", "ReplicaFrameVersion = 2"
        )
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], bad
        )
        assert any("VERSION" in v.message for v in got)

    def test_replication_header_len_drift_caught(self, sources):
        bad = sources["replica"].replace(
            "ReplicaHeaderLen    = 34", "ReplicaHeaderLen    = 30"
        )
        assert bad != sources["replica"]
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], bad
        )
        assert any("ReplicaHeaderLen" in v.message for v in got)

    def test_replication_wirecheck_mirror_drift_caught(self, sources):
        bad = sources["wirecheck"].replace(
            '("stamp_us", 8),', '("stamp_us", 4),'
        )
        assert bad != sources["wirecheck"]
        got = wire_contract.check_replication_framing(
            sources["codec"], bad, sources["replica"]
        )
        assert any("REPLICA_FRAME_FIELDS" in v.message for v in got)

    def test_replication_missing_tables_flagged(self, sources):
        got = wire_contract.check_replication_framing(
            "x = 1\n", sources["wirecheck"], sources["replica"]
        )
        assert any("FRAME_FIELDS" in v.message for v in got)
        got = wire_contract.check_replication_framing(
            sources["codec"], "x = 1\n", sources["replica"]
        )
        assert any("REPLICA_FRAME_FIELDS" in v.message for v in got)
        got = wire_contract.check_replication_framing(
            sources["codec"], sources["wirecheck"], "package x\n"
        )
        assert any("replicaFrameFields" in v.message for v in got)

    def test_stale_pb2_caught(self, sources):
        # a field added to the proto but absent from the emitted module
        grown = self._edit(
            sources["proto"],
            "message AssignRequest {\n  string snapshot_id = 1;",
            "message AssignRequest {\n  string snapshot_id = 1;\n"
            "  int64 deadline_ms = 3;",
        )
        got = wire_contract.check_pb2_descriptor(grown)
        assert any(
            "AssignRequest.deadline_ms missing" in v.message for v in got
        )


class TestSuppressionParsing:
    def test_multi_rule_and_reason_forms(self):
        sups = parse_suppressions(
            "x = 1  # koordlint: disable=retrace-hazard\n"
            "# koordlint: disable=broad-except(reason: probe), donation-safety\n"
        )
        assert sups[1] == {"retrace-hazard"}
        assert sups[2] == {"broad-except", "donation-safety"}

    def test_tags_inside_string_literals_are_not_suppressions(self):
        """A docstring or message string MENTIONING the tag must not
        exempt a violation on or below its line — only real comment
        tokens count (the blanket-suppression hole the tool's docstring
        promises cannot happen)."""
        # the string literal sits on the line directly above the
        # handler — exactly where a real tag would suppress it
        got = lint('''
        def g():
            try:
                x = "# koordlint: disable=broad-except(<reason>)"
            except Exception:
                pass
        ''')
        assert [(v.rule, v.line) for v in got] == [("broad-except", 5)]
        # the same text as a REAL comment does suppress
        assert lint('''
        def g():
            try:
                risky()
            # koordlint: disable=broad-except(probe)
            except Exception:
                pass
        ''') == []

    def test_reason_text_cannot_leak_into_rule_set(self):
        # rule-shaped words INSIDE a reason must not suppress other rules,
        # even with a space before the parenthesis
        sups = parse_suppressions(
            "# koordlint: disable=broad-except (retrace-hazard noise here)\n"
            "# koordlint: disable=broad-except(x) because donation-safety\n"
        )
        assert sups[1] == {"broad-except"}
        assert sups[2] == {"broad-except"}

    def test_find_repo_root(self):
        assert find_repo_root(os.path.join(REPO, "tests")) == REPO


class TestGoToolchainGate:
    """`go vet` + `gofmt -l` for go/ when a toolchain exists; skip (not
    fail) when absent — the protoc-skip convention from PR 1."""

    def test_gofmt_clean(self):
        gofmt = shutil.which("gofmt")
        if gofmt is None:
            pytest.skip("no Go toolchain in this image (gofmt absent)")
        out = subprocess.run(
            [gofmt, "-l", os.path.join(REPO, "go")],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "", (
            f"gofmt would reformat: {out.stdout}"
        )

    def test_go_vet_scorerclient(self):
        go = shutil.which("go")
        if go is None:
            pytest.skip("no Go toolchain in this image (go absent)")
        proc = subprocess.run(
            [go, "vet", "./..."],
            cwd=os.path.join(REPO, "go", "scorerclient"),
            capture_output=True, text=True, timeout=300,
        )
        if proc.returncode != 0 and re.search(
            r"(no required module provides|missing go\.sum|dial tcp|"
            r"cannot find module|proxy\.golang\.org|connection refused)",
            proc.stderr,
        ):
            pytest.skip(
                "go vet needs the module graph and the network is "
                f"unavailable: {proc.stderr.strip()[:200]}"
            )
        assert proc.returncode == 0, proc.stderr


class TestUnboundedWait:
    """unbounded-wait (ISSUE 13): no-timeout Condition/Event waits and
    deadline-less client stub calls fail lint; the backstop idiom,
    bounded waits and reasoned suppressions stay clean."""

    def test_bare_event_wait_caught(self):
        out = lint(
            """
            import threading

            def park(done):
                done.wait()
            """,
            ["unbounded-wait"],
        )
        assert len(out) == 1
        assert out[0].rule == "unbounded-wait"
        assert "timeout" in out[0].message

    def test_bare_condition_wait_caught(self):
        out = lint(
            """
            def drain(self):
                with self._cond:
                    while not self._frames:
                        self._cond.wait()
            """,
            ["unbounded-wait"],
        )
        assert len(out) == 1

    def test_backstop_idiom_clean(self):
        out = lint(
            """
            def follow(self, entry):
                with self._cond:
                    while not entry.done:
                        self._cond.wait(timeout=1.0)
                while not entry.flag.wait(timeout=1.0):
                    pass
            """,
            ["unbounded-wait"],
        )
        assert out == []

    def test_positional_timeout_clean(self):
        out = lint(
            """
            def join(self, t):
                t.wait(5.0)
            """,
            ["unbounded-wait"],
        )
        assert out == []

    def test_stub_call_without_timeout_caught(self):
        out = lint(
            """
            def call(self, request):
                stub = self._score_stub()
                return stub(request)
            """,
            ["unbounded-wait"],
        )
        assert len(out) == 1
        assert "stub" in out[0].message

    def test_stub_call_with_timeout_clean(self):
        out = lint(
            """
            def call(self, request):
                stub = self._score_stub()
                return stub(request, timeout=self._timeout_s())
            """,
            ["unbounded-wait"],
        )
        assert out == []

    def test_stub_factory_call_clean(self):
        # zero-arg calls are stub FACTORIES, not RPC invocations
        out = lint(
            """
            def pick(self):
                return self._leader_score_stub()
            """,
            ["unbounded-wait"],
        )
        assert out == []

    def test_kwargs_splat_not_flagged(self):
        # a **kw splat may carry timeout=: cannot prove it missing
        out = lint(
            """
            def call(self, request, **kw):
                return stub(request, **kw)
            """,
            ["unbounded-wait"],
        )
        assert out == []

    def test_suppression_honored(self):
        out = lint(
            """
            import threading

            def main():
                threading.Event().wait()  # koordlint: disable=unbounded-wait(main thread parks forever by design)
            """,
            ["unbounded-wait"],
        )
        assert out == []


class TestWirecheckMessageMirror:
    """wire-contract's third-mirror extension (ISSUE 13): the
    hand-rolled wirecheck.py decoders are statically diffed against
    the proto so a new field (the deadline/band/degraded additions
    being the motivating case) cannot be silently dropped by the
    independent mirror."""

    PROTO = """
    message ScoreRequest {
      string snapshot_id = 1;
      int64 top_k = 2;
      bool flat = 3;
      int64 deadline_ms = 4;
    }
    """

    GOOD = '''
def decode_score_request(b):
    r = {"snapshot_id": "", "top_k": 0, "flat": False, "deadline_ms": 0}
    for field, _wtype, v in split_fields(b):
        if field == 1:
            r["snapshot_id"] = v.decode("utf-8")
        elif field == 2:
            r["top_k"] = _signed(v)
        elif field == 3:
            r["flat"] = bool(v)
        elif field == 4:
            r["deadline_ms"] = _signed(v)
    return r
'''

    def test_matching_mirror_clean(self):
        out = wire_contract.check_wirecheck_messages(
            textwrap.dedent(self.PROTO), self.GOOD
        )
        assert out == []

    def test_missing_branch_caught(self):
        src = self.GOOD.replace(
            '        elif field == 4:\n'
            '            r["deadline_ms"] = _signed(v)\n', ''
        )
        out = wire_contract.check_wirecheck_messages(
            textwrap.dedent(self.PROTO), src
        )
        assert len(out) == 1
        assert "field == 4" in out[0].message
        assert "deadline_ms" in out[0].message

    def test_wrong_key_caught(self):
        src = self.GOOD.replace('r["deadline_ms"] = _signed(v)',
                                'r["deadline"] = _signed(v)')
        out = wire_contract.check_wirecheck_messages(
            textwrap.dedent(self.PROTO), src
        )
        assert len(out) == 1
        assert "deadline_ms" in out[0].message

    def test_phantom_field_caught(self):
        src = self.GOOD.replace(
            'elif field == 4:',
            'elif field == 9:\n            r["ghost"] = v\n'
            '        elif field == 4:'
        )
        out = wire_contract.check_wirecheck_messages(
            textwrap.dedent(self.PROTO), src
        )
        assert len(out) == 1
        assert "field 9" in out[0].message

    def test_missing_decoder_caught(self):
        out = wire_contract.check_wirecheck_messages(
            textwrap.dedent(self.PROTO), "def unrelated():\n    pass\n"
        )
        assert len(out) == 1
        assert "decode_score_request" in out[0].message

    def test_repo_wirecheck_mirror_is_clean(self):
        out = wire_contract.check_wirecheck_messages(
            read("koordinator_tpu", "bridge", "scorer.proto"),
            read("koordinator_tpu", "bridge", "wirecheck.py"),
        )
        assert out == [], "\n".join(v.format() for v in out)


# ---- ISSUE 17: whole-program lock graph + guarded-state inference ----


def lockcheck(sources, md_text="GENERATE"):
    """Run the lock-graph pass over synthetic sources.  By default the
    doc is generated from the same graph, so only cycle / witness-name
    violations surface; pass explicit md_text (or None) to exercise the
    drift directions."""
    from koordinator_tpu.analysis import lockgraph

    srcs = {p: textwrap.dedent(s) for p, s in sources.items()}
    if md_text == "GENERATE":
        md_text = lockgraph.generate_lockorder_md(lockgraph.build_graph(srcs))
    return lockgraph.check_sources(srcs, md_text)


class TestLockOrderCycle:
    def test_direct_nesting_cycle_caught(self):
        got = lockcheck({"eng.py": """
        import threading

        class Engine:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        pass

            def rev(self):
                with self._b:
                    with self._a:
                        pass
        """})
        assert [v.rule for v in got] == ["lock-order-cycle"]
        assert "eng.Engine._a" in got[0].message
        assert "eng.Engine._b" in got[0].message
        assert "deadlock" in got[0].message

    def test_cross_module_call_cycle_caught(self):
        # neither module nests both locks lexically: the cycle only
        # exists through the cross-module method table
        got = lockcheck({
            "pmod.py": """
            import threading
            from qmod import Q

            class P:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.q = Q(self)

                def outer(self):
                    with self._lock:
                        self.q.enter_q()

                def locked_op(self):
                    with self._lock:
                        pass
            """,
            "qmod.py": """
            import threading

            class Q:
                def __init__(self, p):
                    self._lock = threading.Lock()
                    self.p = p

                def enter_q(self):
                    with self._lock:
                        pass

                def back(self):
                    with self._lock:
                        self.p.locked_op()
            """,
        })
        assert [v.rule for v in got] == ["lock-order-cycle"]
        assert "pmod.P._lock" in got[0].message
        assert "qmod.Q._lock" in got[0].message

    def test_condition_wait_reacquire_closes_cycle(self):
        # cond -> y from the nesting, and wait() re-acquires cond while
        # y is STILL held (the stdlib releases only the condition):
        # y -> cond — the hidden inversion a plain `with cond:` in
        # another thread deadlocks against
        got = lockcheck({"w.py": """
        import threading

        class W:
            def __init__(self):
                self._y = threading.Lock()
                self._cond = threading.Condition()

            def park(self):
                with self._cond:
                    with self._y:
                        self._cond.wait(timeout=1.0)
        """})
        assert [v.rule for v in got] == ["lock-order-cycle"]
        assert "Condition.wait reacquire" in got[0].message

    def test_lexical_acquire_holds_rest_of_block(self):
        # .acquire() (no with) still orders later acquisitions
        got = lockcheck({"acq.py": """
        import threading

        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m(self):
                self._a.acquire()
                with self._b:
                    pass
                self._a.release()

            def n(self):
                with self._b:
                    with self._a:
                        pass
        """})
        assert [v.rule for v in got] == ["lock-order-cycle"]

    def test_clean_hierarchy_passes(self):
        got = lockcheck({"clean.py": """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self._c = threading.Lock()

            def m(self):
                with self._a:
                    with self._b:
                        with self._c:
                            pass

            def n(self):
                with self._b:
                    with self._c:
                        pass
        """})
        assert got == [], "\n".join(v.format() for v in got)

    def test_same_identity_nesting_is_not_a_cycle(self):
        # two instances share one identity; self-edges carry no order
        got = lockcheck({"dup.py": """
        import threading

        class D:
            def __init__(self):
                self._lock = threading.Lock()

            def m(self, other):
                with self._lock:
                    with other._lock:
                        pass
        """})
        assert got == [], "\n".join(v.format() for v in got)


class TestLockOrderDocDrift:
    TWO_LOCKS = {
        "two.py": """
        import threading

        class T:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m(self):
                with self._a:
                    with self._b:
                        pass
        """,
    }

    def test_missing_doc_fails(self):
        got = lockcheck(self.TWO_LOCKS, md_text=None)
        assert [v.rule for v in got] == ["lockorder-doc-drift"]
        assert "not found" in got[0].message

    def test_derived_edge_missing_from_doc_fails(self):
        from koordinator_tpu.analysis import lockgraph

        srcs = {p: textwrap.dedent(s) for p, s in self.TWO_LOCKS.items()}
        md = lockgraph.generate_lockorder_md(lockgraph.build_graph(srcs))
        gutted = "\n".join(
            ln for ln in md.splitlines() if " | `two.T._b` | " not in ln
        )
        got = lockcheck(self.TWO_LOCKS, md_text=gutted)
        assert any("missing from" in v.message and v.rule ==
                   "lockorder-doc-drift" for v in got)

    def test_doc_edge_nothing_derives_fails(self):
        from koordinator_tpu.analysis import lockgraph

        srcs = {p: textwrap.dedent(s) for p, s in self.TWO_LOCKS.items()}
        md = lockgraph.generate_lockorder_md(lockgraph.build_graph(srcs))
        # a phantom reversed edge row nothing derives
        md += "| `two.T._b` | `two.T._a` | two.py:1 | nested with |\n"
        got = lockcheck(self.TWO_LOCKS, md_text=md)
        assert any("no code path derives" in v.message for v in got)

    def test_byte_stale_doc_fails(self):
        from koordinator_tpu.analysis import lockgraph

        srcs = {p: textwrap.dedent(s) for p, s in self.TWO_LOCKS.items()}
        md = lockgraph.generate_lockorder_md(lockgraph.build_graph(srcs))
        got = lockcheck(self.TWO_LOCKS, md_text=md + "\ntrailing edit\n")
        assert [v.rule for v in got] == ["lockorder-doc-drift"]
        assert "stale" in got[0].message

    def test_witness_factory_name_mismatch_fails(self):
        got = lockcheck({"wn.py": """
        from koordinator_tpu.obs.lockwitness import witness_lock

        class N:
            def __init__(self):
                self._lock = witness_lock("wrong.identity")
        """})
        assert any("witness factory" in v.message and v.rule ==
                   "lockorder-doc-drift" for v in got)
        assert any("wn.N._lock" in v.message for v in got)

    def test_witness_factory_correct_name_passes(self):
        got = lockcheck({"wn.py": """
        from koordinator_tpu.obs.lockwitness import witness_lock

        class N:
            def __init__(self):
                self._lock = witness_lock("wn.N._lock")
        """})
        assert got == [], "\n".join(v.format() for v in got)

    def test_repo_doc_regenerates_byte_identical(self):
        from koordinator_tpu.analysis import lockgraph

        want = lockgraph.generate_lockorder_md(lockgraph.repo_graph(REPO))
        assert read("docs", "LOCKORDER.md") == want, (
            "docs/LOCKORDER.md is stale — run "
            "`python -m koordinator_tpu.analysis --write-lockorder`"
        )


class TestUnguardedSharedState:
    def test_lock_free_write_of_guarded_attr_caught(self):
        got = lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0

            def bump(self):
                with self._lock:
                    self._count += 1

            def reset(self):
                self._count = 0
        """, ["unguarded-shared-state"])
        assert [(v.rule, v.line) for v in got] == \
            [("unguarded-shared-state", 14)]
        assert "two writers race" in got[0].message

    def test_lock_free_read_of_mutated_structure_caught(self):
        got = lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}

            def put(self, k, v):
                with self._lock:
                    self._items[k] = v

            def dump(self):
                return list(self._items.values())
        """, ["unguarded-shared-state"])
        assert len(got) == 1
        assert "mutated in place" in got[0].message

    def test_init_writes_exempt(self):
        got = lint("""
        import threading

        class S:
            def __init__(self, seed):
                self._lock = threading.Lock()
                self._count = seed
                self._count += 1

            def bump(self):
                with self._lock:
                    self._count += 1
        """, ["unguarded-shared-state"])
        assert got == [], "\n".join(v.format() for v in got)

    def test_rebind_only_attr_atomic_read_exempt(self):
        # the guarded writes only REBIND (no in-place mutation): a
        # lock-free read sees either the old or the new object — atomic
        got = lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._snapshot = None

            def publish(self, snap):
                with self._lock:
                    self._snapshot = snap

            def peek(self):
                return self._snapshot
        """, ["unguarded-shared-state"])
        assert got == [], "\n".join(v.format() for v in got)

    def test_locked_suffix_method_exempt(self):
        # *_locked methods run with the lock already held by contract
        got = lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0

            def bump(self):
                with self._lock:
                    self._bump_locked()

            def _bump_locked(self):
                self._count += 1
        """, ["unguarded-shared-state"])
        assert got == [], "\n".join(v.format() for v in got)

    def test_reasoned_suppression_honored(self):
        got = lint("""
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0

            def bump(self):
                with self._lock:
                    self._count += 1

            def reset(self):
                # koordlint: disable=unguarded-shared-state(reason: boot only)
                self._count = 0
        """, ["unguarded-shared-state"])
        assert got == [], "\n".join(v.format() for v in got)

    def test_class_without_lock_ignored(self):
        got = lint("""
        class Plain:
            def __init__(self):
                self._count = 0

            def bump(self):
                self._count += 1
        """, ["unguarded-shared-state"])
        assert got == [], "\n".join(v.format() for v in got)


class TestSuppressionAudit:
    def test_parse_tags_extracts_rules_and_reasons(self):
        from koordinator_tpu.analysis import suppressions

        tags = suppressions.parse_tags("f.py", textwrap.dedent("""
        x = 1  # koordlint: disable=broad-except(reason: logged upstream)
        y = 2  # koordlint: disable=bare-retry
        # koordlint: disable=broad-except(a), unbounded-wait
        """))
        got = [(t.line, t.rule, t.reason) for t in tags]
        assert got == [
            (2, "broad-except", "reason: logged upstream"),
            (3, "bare-retry", None),
            (4, "broad-except", "a"),
            (4, "unbounded-wait", None),
        ]

    def test_repo_audit_is_clean(self):
        from koordinator_tpu.analysis import suppressions

        tags, problems = suppressions.audit(REPO)
        assert problems == [], "\n".join(p.format() for p in problems)
        # every reason-required tag in the repo carries its reason
        for tag in tags:
            if tag.rule in suppressions.REASON_REQUIRED:
                assert tag.reason, f"{tag.path}:{tag.line} missing reason"

    def test_cli_suppressions_flag_exits_zero(self, capsys):
        from koordinator_tpu.analysis.__main__ import main

        assert main(["--suppressions", "--root", REPO]) == 0
        out = capsys.readouterr().out
        assert "live suppression(s)" in out
        assert "audit clean" in out

    def test_format_report_flags_problems(self):
        from koordinator_tpu.analysis.core import Violation
        from koordinator_tpu.analysis import suppressions

        report = suppressions.format_report(
            [suppressions.Tag("f.py", 3, "broad-except", None)],
            [Violation("suppression-audit", "f.py", 3, "no reason")],
        )
        assert "NO REASON" in report
        assert "AUDIT FAILED: 1 problem(s)" in report


class TestUnregisteredJitBoundary:
    """Seeded regressions for the device-time-truth rule (ISSUE 19):
    serving-path jit boundaries must register with the launch ledger."""

    R = ["unregistered-jit-boundary"]

    def _lint(self, src, path="koordinator_tpu/solver/fixture.py"):
        return run_rules_on_source(path, textwrap.dedent(src), self.R)

    def test_unregistered_jitted_def_fires(self):
        vs = self._lint("""
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("cfg",))
        def _score(snapshot, cfg):
            return snapshot
        """)
        assert len(vs) == 1
        assert "no @devprof.boundary" in vs[0].message

    def test_registered_jitted_def_is_clean(self):
        vs = self._lint("""
        from functools import partial
        import jax
        from koordinator_tpu.obs import devprof

        @devprof.boundary("solver.fixture._score")
        @partial(jax.jit, static_argnames=("cfg",))
        def _score(snapshot, cfg):
            return snapshot
        """)
        assert vs == []

    def test_boundary_below_jit_fires_order_violation(self):
        # decorators apply bottom-up: boundary below jit wraps the raw
        # function and the AOT compile capture has nothing to .lower()
        vs = self._lint("""
        from functools import partial
        import jax
        from koordinator_tpu.obs import devprof

        @partial(jax.jit, static_argnames=("cfg",))
        @devprof.boundary("solver.fixture._score")
        def _score(snapshot, cfg):
            return snapshot
        """)
        assert len(vs) == 1
        assert "BELOW" in vs[0].message

    def test_non_literal_boundary_name_fires(self):
        vs = self._lint("""
        import jax
        from koordinator_tpu.obs import devprof

        NAME = "solver.fixture._score"

        @devprof.boundary(NAME)
        @jax.jit
        def _score(x):
            return x
        """)
        assert len(vs) == 1
        assert "string literal" in vs[0].message

    def test_jit_call_form_assignment_fires(self):
        vs = self._lint("""
        import jax

        def _scatter(arr, idx):
            return arr

        scatter = jax.jit(_scatter, donate_argnums=(0,))
        """)
        assert len(vs) == 1
        assert "call-form" in vs[0].message

    def test_shard_map_outside_jit_fires(self):
        vs = self._lint("""
        import jax

        def helper(mesh, x):
            return jax.shard_map(
                lambda a: a, mesh=mesh, in_specs=None, out_specs=None
            )(x)
        """)
        assert len(vs) == 1
        assert "shard_map launch outside" in vs[0].message

    def test_shard_map_inside_registered_jit_is_clean(self):
        vs = self._lint("""
        from functools import partial
        import jax
        from koordinator_tpu.obs import devprof

        @devprof.boundary("solver.fixture._sharded")
        @partial(jax.jit, static_argnames=("mesh",))
        def _sharded(x, *, mesh):
            return jax.shard_map(
                lambda a: a, mesh=mesh, in_specs=None, out_specs=None
            )(x)
        """)
        assert vs == []

    def test_out_of_scope_module_is_exempt(self):
        # harness/test modules never sit on the Score/Assign path
        vs = self._lint("""
        import jax

        @jax.jit
        def scenario_kernel(x):
            return x
        """, path="koordinator_tpu/harness/fixture.py")
        assert vs == []

    def test_suppression_with_reason_is_honored(self):
        vs = self._lint("""
        import jax

        @jax.jit  # koordlint: disable=unregistered-jit-boundary(reason: cold-path migration helper, never on the serving path)
        def _migrate(x):
            return x
        """)
        assert vs == []

    def test_reason_required_for_suppression(self):
        from koordinator_tpu.analysis import suppressions

        assert "unregistered-jit-boundary" in suppressions.REASON_REQUIRED


class TestPrewarmDrift:
    """ISSUE 20: the prewarm tables in obs/prewarm.py partition the
    registered boundary space — one-sided drift against the repo's
    ``@devprof.boundary`` registrations must fail lint in BOTH
    directions (the metrics-doc-drift shape applied to the AOT replay
    contract)."""

    REGISTRATIONS = [
        ("solver.candidates._build", "koordinator_tpu/solver/candidates.py", 10),
        ("solver.candidates._build_sharded", "koordinator_tpu/solver/candidates.py", 20),
        ("solver.topk.masked_top_k", "koordinator_tpu/solver/topk.py", 5),
    ]
    PREWARM_FIXTURE = textwrap.dedent('''
        PREWARM_BOUNDARIES = (
            "solver.candidates._build",
            "solver.topk.masked_top_k",
        )

        PREWARM_EXCLUDED = {
            "solver.candidates._build_sharded": "mesh static is process-local",
        }
    ''')

    def test_aligned_sources_are_clean(self):
        from koordinator_tpu.analysis import prewarmdrift

        assert prewarmdrift.diff_prewarm(
            self.REGISTRATIONS, self.PREWARM_FIXTURE
        ) == []

    def test_head_is_clean(self):
        from koordinator_tpu.analysis import prewarmdrift

        root = find_repo_root(REPO)
        assert prewarmdrift.check_repo(root) == []

    def test_registered_but_untabled_caught(self):
        from koordinator_tpu.analysis import prewarmdrift

        regs = self.REGISTRATIONS + [
            ("solver.wave._resolve_jit", "koordinator_tpu/solver/wave.py", 42),
        ]
        got = prewarmdrift.diff_prewarm(regs, self.PREWARM_FIXTURE)
        assert len(got) == 1
        assert got[0].rule == "prewarm-drift"
        assert "solver.wave._resolve_jit" in got[0].message
        assert "absent from both prewarm tables" in got[0].message
        # flags the registration's own file and line
        assert got[0].path.endswith("wave.py")
        assert got[0].line == 42

    def test_stale_replay_row_caught(self):
        from koordinator_tpu.analysis import prewarmdrift

        regs = [r for r in self.REGISTRATIONS
                if r[0] != "solver.topk.masked_top_k"]
        got = prewarmdrift.diff_prewarm(regs, self.PREWARM_FIXTURE)
        assert len(got) == 1
        assert "solver.topk.masked_top_k" in got[0].message
        assert "stale replay row" in got[0].message
        # flags the prewarm.py table entry's line
        assert got[0].path.endswith("prewarm.py")
        assert got[0].line > 0

    def test_stale_exclusion_caught(self):
        from koordinator_tpu.analysis import prewarmdrift

        regs = [r for r in self.REGISTRATIONS
                if r[0] != "solver.candidates._build_sharded"]
        got = prewarmdrift.diff_prewarm(regs, self.PREWARM_FIXTURE)
        assert len(got) == 1
        assert "stale exclusion" in got[0].message
        assert got[0].path.endswith("prewarm.py")

    def test_double_listing_caught(self):
        from koordinator_tpu.analysis import prewarmdrift

        both = self.PREWARM_FIXTURE.replace(
            '"solver.candidates._build",',
            '"solver.candidates._build",\n    '
            '"solver.candidates._build_sharded",',
        )
        got = prewarmdrift.diff_prewarm(self.REGISTRATIONS, both)
        assert any(
            "BOTH" in v.message
            and "solver.candidates._build_sharded" in v.message
            for v in got
        )

    def test_registration_parser_skips_docstring_examples(self):
        from koordinator_tpu.analysis import prewarmdrift

        src = textwrap.dedent('''
            from koordinator_tpu.obs import devprof

            def helper():
                """Example:

                    @devprof.boundary("solver.fake.from_docstring")
                    def f(x): ...
                """

            @devprof.boundary("solver.real.registered")
            def real(x):
                return x
        ''')
        got = prewarmdrift.parse_boundary_registrations(src)
        assert [name for name, _ in got] == ["solver.real.registered"]

    def test_vanished_tables_fail_loudly(self):
        from koordinator_tpu.analysis import prewarmdrift

        got = prewarmdrift.diff_prewarm(self.REGISTRATIONS, "X = 1\n")
        assert any(
            "no PREWARM_BOUNDARIES / PREWARM_EXCLUDED" in v.message
            for v in got
        )

    def test_rule_is_registered_and_runs_in_run_repo(self):
        assert "prewarm-drift" in RULES
        assert run_repo(root=REPO, rules=["prewarm-drift"]) == []
