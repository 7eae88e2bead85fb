"""Fused scoring-term registry (ISSUE 15).

The scorer reproduced only NodeResourcesFit/LoadAware/NUMA; PAPERS.md
names the workloads that make a batched TPU scorer worth having —
Gavel-style heterogeneity policies (2008.09213, per-(job class,
accelerator type) throughput matrices), Synergy-style CPU/mem
sensitivity profiles (2110.06073) and constraint-based bin packing
(2511.08373).  The repo's perf claim is "one dense pods x nodes launch,
no per-plugin loops", so new policies land as **fused tensor terms**
inside the existing ``score_all`` body — zero extra launches, zero
extra readbacks — never as sequential per-plugin passes the way the Go
reference runs its plugin chain (``bench.py --config plugins`` measures
the fused engine against exactly that per-term-sequential oracle).

The term contract (docs/KERNEL.md "Scoring terms"):

* **cellwise** — a term's score/mask contribution at cell (p, n) reads
  only pod row p, node row n, and replicated side tables (the
  throughput matrix).  This is the invariant that keeps the incremental
  engine exact: ``rescore_dirty``'s gather-compute-scatter re-derives
  the very same bits a full rescore would put in the dirty cells.
* **dirty-attributable** — every tensor a term reads must map a delta
  Sync onto score rows/columns (bridge/state.py ``_score_dirty_rows``:
  sensitivity deltas dirty pod rows, a throughput-matrix delta dirties
  the nodes of the touched accelerator type, accel/workload column
  flips diff per row).
* **statically bounded** — each term's contribution clamps to
  ``[0, weight * MAX_NODE_SCORE]`` on device, so
  :func:`terms_upper_bound` is a CONFIG property and the f32-exact
  serving top-k fast path (solver/topk.py) keeps running with terms on;
  a data tensor violating the clamp cannot mis-order the reply (the
  runtime in-bound cond takes the integer path).

The registry generalizes the ``extra_mask``/``extra_scores`` seam
(solver/greedy.py:240): ``apply_terms`` fuses contributions into
``score_all``'s one tensor program, and ``term_extras`` materializes
the same cellwise tensors once per Assign cycle so the sequential
engines — the scan and the Pallas kernel — consume the fused total
through the seam they already have.  Missing snapshot
data (a term enabled before its tensors synced) contributes nothing:
enabling a term must never fault a cycle, only inform it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from koordinator_tpu.model.snapshot import MAX_NODE_SCORE
from koordinator_tpu.obs import devprof
from koordinator_tpu.ops.scoring import (
    most_requested_score,
    weighted_resource_score,
)


@dataclasses.dataclass(frozen=True)
class TermSpec:
    """One registered scoring term.

    ``enabled(cfg)``    — whether the CycleConfig turns the term on.
    ``score(snap, cfg)``— cellwise i64[P, N] score contribution, or
                          None (no data synced yet; the term is inert).
    ``mask(snap, cfg)`` — cellwise bool[P, N] feasibility mask, or None.
    ``upper_bound(cfg)``— static max of the score contribution; summed
                          into solver/topk.py ``score_upper_bound``.
    ``has_mask(cfg)``   — pure config predicate: whether ``mask`` would
                          return a tensor (so callers can size the jit
                          signature without tracing).
    """

    name: str
    enabled: Callable
    score: Callable
    mask: Callable
    upper_bound: Callable
    has_mask: Callable = staticmethod(lambda cfg: False)


def _clip_term(raw: jnp.ndarray, weight: int) -> jnp.ndarray:
    """The per-term clamp that makes the bound a config property."""
    return int(weight) * jnp.clip(
        raw.astype(jnp.int64), 0, MAX_NODE_SCORE
    )


# ---------------------------------------------------------------------------
# heterogeneity — Gavel-style throughput matrix (2008.09213)
# ---------------------------------------------------------------------------


def _het_score(snapshot, cfg):
    tput = getattr(snapshot, "throughput", None)
    if tput is None:
        return None
    wclass = snapshot.pods.workload_class
    accel = snapshot.nodes.accel_type
    C, A = tput.shape
    c = (
        jnp.clip(wclass.astype(jnp.int64), 0, C - 1)
        if wclass is not None
        else jnp.zeros(snapshot.pods.requests.shape[0], jnp.int64)
    )
    a = (
        jnp.clip(accel.astype(jnp.int64), 0, A - 1)
        if accel is not None
        else jnp.zeros(snapshot.nodes.allocatable.shape[0], jnp.int64)
    )
    raw = tput[c[:, None], a[None, :]]  # [P, N] gather
    return _clip_term(raw, cfg.heterogeneity.weight)


# ---------------------------------------------------------------------------
# sensitivity — Synergy-style CPU/mem profiles (2110.06073)
# ---------------------------------------------------------------------------


def _sens_score(snapshot, cfg):
    sens = snapshot.pods.sensitivity
    if sens is None:
        return None
    nodes = snapshot.nodes
    alloc = nodes.allocatable.astype(jnp.int64)
    usage = nodes.usage.astype(jnp.int64)
    safe_cap = jnp.where(alloc == 0, 1, alloc)
    # occupancy percent per (node, resource), clamped: a node reporting
    # usage past allocatable saturates at 100, an unallocatable resource
    # reads as empty (nothing to contend on)
    occ = jnp.clip(usage * MAX_NODE_SCORE // safe_cap, 0, MAX_NODE_SCORE)
    occ = jnp.where(alloc == 0, 0, occ)
    s = jnp.clip(sens.astype(jnp.int64), 0, MAX_NODE_SCORE)  # [P, R]
    s_sum = jnp.sum(s, axis=-1)  # [P]
    contention = (
        jnp.einsum("pr,nr->pn", s, occ) // jnp.maximum(s_sum, 1)[:, None]
    )
    # a pod with an all-zero profile is insensitive: contention 0, full
    # score — exactly the no-profile pod's treatment
    raw = MAX_NODE_SCORE - contention
    return _clip_term(raw, cfg.sensitivity.weight)


# ---------------------------------------------------------------------------
# packing — bin-packing objective + headroom mask (2511.08373)
# ---------------------------------------------------------------------------


def _pack_score(snapshot, cfg):
    nodes, pods = snapshot.nodes, snapshot.pods
    t = nodes.requested[None, :, :] + pods.requests[:, None, :]
    per_res = most_requested_score(t, nodes.allocatable[None, :, :])
    raw = weighted_resource_score(per_res, cfg.packing.weights_arr())
    return _clip_term(raw, cfg.packing.weight)


def _pack_masks(cfg) -> bool:
    """Whether the packing term contributes a mask — a pure CONFIG
    predicate (headroom is a frozen tuple), so callers can ask without
    tracing anything."""
    return any(int(v) > 0 for _, v in cfg.packing.headroom)


def _pack_mask(snapshot, cfg):
    if not _pack_masks(cfg):
        return None
    head = cfg.packing.headroom_arr()  # i64[R]; 0 = unconstrained
    nodes, pods = snapshot.nodes, snapshot.pods
    alloc = nodes.allocatable.astype(jnp.int64)
    post = (
        nodes.requested.astype(jnp.int64)[None, :, :]
        + pods.requests.astype(jnp.int64)[:, None, :]
    )
    limited = head[None, None, :] > 0
    ok = post * 100 <= head[None, None, :] * alloc[None, :, :]
    return jnp.all(jnp.where(limited, ok, True), axis=-1)


def _weight_bound(weight) -> int:
    return MAX_NODE_SCORE * int(weight)


TERMS: Tuple[TermSpec, ...] = (
    TermSpec(
        name="heterogeneity",
        enabled=lambda cfg: cfg.heterogeneity is not None,
        score=_het_score,
        mask=lambda snapshot, cfg: None,
        upper_bound=lambda cfg: _weight_bound(cfg.heterogeneity.weight),
    ),
    TermSpec(
        name="sensitivity",
        enabled=lambda cfg: cfg.sensitivity is not None,
        score=_sens_score,
        mask=lambda snapshot, cfg: None,
        upper_bound=lambda cfg: _weight_bound(cfg.sensitivity.weight),
    ),
    TermSpec(
        name="packing",
        enabled=lambda cfg: cfg.packing is not None,
        score=_pack_score,
        mask=_pack_mask,
        upper_bound=lambda cfg: _weight_bound(cfg.packing.weight),
        has_mask=_pack_masks,
    ),
)


def enabled_terms(cfg) -> Tuple[TermSpec, ...]:
    return tuple(t for t in TERMS if t.enabled(cfg))


def terms_upper_bound(cfg) -> int:
    """Static upper bound of the summed enabled-term contributions —
    the term-aware half of solver/topk.py ``score_upper_bound``."""
    return sum(t.upper_bound(cfg) for t in enabled_terms(cfg))


def apply_term_scores(snapshot, cfg, scores):
    """The SCORE half of the term stack: fold every enabled term's
    cellwise score contribution into ``scores``.  Factored out of
    :func:`apply_terms` (ISSUE 16) so the sparse candidate engine can
    run the mask half standalone (feasibility pre-mask) and the score
    half over gathered [P, C] cells; additions commute, so the split
    is bitwise identical to the fused loop."""
    for term in enabled_terms(cfg):
        s = term.score(snapshot, cfg)
        if s is not None:
            scores = scores + s
    return scores


def apply_term_masks(snapshot, cfg, feasible):
    """The MASK half of the term stack: AND every enabled term's
    cellwise feasibility mask into ``feasible`` — the term piece of the
    standalone feasibility pre-mask (solver/greedy.py
    ``feasibility_mask``, ISSUE 16).  ANDs commute, so running this
    apart from the score half changes no bits."""
    for term in enabled_terms(cfg):
        m = term.mask(snapshot, cfg)
        if m is not None:
            feasible = feasible & m
    return feasible


def apply_terms(snapshot, cfg, scores, feasible):
    """Fuse every enabled term's cellwise contribution into the
    (scores, feasible) pair INSIDE the one tensor program — called from
    ``score_all`` (solver/greedy.py), so score_cycle, the incremental
    column/row rescore and the sharded rescore all carry the terms with
    zero extra launches.  Shape-polymorphic over gathered sub-snapshots
    (the incremental engine scores [P, d] and [d_p, N] blocks through
    the same body).  Composed from the score/mask halves: the halves
    commute (adds with adds, ANDs with ANDs), so the sparse engine's
    standalone mask pass stays bit-identical to this fused loop."""
    return (
        apply_term_scores(snapshot, cfg, scores),
        apply_term_masks(snapshot, cfg, feasible),
    )


@devprof.boundary("solver.terms._term_extras_jit")
@partial(jax.jit, static_argnames=("cfg",))
def _term_extras_jit(snapshot, cfg):
    P = snapshot.pods.requests.shape[0]
    N = snapshot.nodes.allocatable.shape[0]
    scores = jnp.zeros((P, N), jnp.int64)
    feasible = jnp.ones((P, N), bool)
    return apply_terms(snapshot, cfg, scores, feasible)


def term_extras(snapshot, cfg):
    """(extra_scores, extra_mask) [P, N] tensors of the enabled terms —
    the fused total the sequential Assign engines consume through the
    existing ``extra_mask``/``extra_scores`` seam (greedy scan, the
    Pallas kernel).  Returns (None, None) with no
    terms enabled, so untermed configs pay nothing; otherwise ONE jit
    launch (async, no readback) whose cache keys only on (geometry,
    cfg).  The mask half is None when no enabled term masks (an
    all-True mask would widen the jit signature for nothing)."""
    terms = enabled_terms(cfg)
    if not terms:
        return None, None
    scores, feasible = _term_extras_jit(snapshot, cfg)
    has_mask = any(t.has_mask(cfg) for t in terms)
    return scores, (feasible if has_mask else None)


def term_names(cfg) -> Tuple[str, ...]:
    """Enabled term names (telemetry: koord_scorer_term_total{term})."""
    return tuple(t.name for t in enabled_terms(cfg))


def default_term_config(base=None, packing_headroom=None):
    """A CycleConfig with all three registry terms enabled — the shape
    the trace harness, the bench ``--config plugins`` child and the
    parity fuzz all drive.  ``base`` seeds every non-term field;
    ``packing_headroom`` (resource -> max utilization percent) turns
    the packing MASK on as well as its score."""
    import dataclasses as _dc

    from koordinator_tpu.config import (
        CycleConfig,
        HeterogeneityTermArgs,
        PackingTermArgs,
        SensitivityTermArgs,
    )

    base = base if base is not None else CycleConfig()
    return _dc.replace(
        base,
        heterogeneity=HeterogeneityTermArgs(),
        sensitivity=SensitivityTermArgs(),
        packing=PackingTermArgs(
            headroom=packing_headroom if packing_headroom else ()
        ),
    )
