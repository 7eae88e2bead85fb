from koordinator_tpu.solver.greedy import (  # noqa: F401
    CycleResult,
    STATUS_ASSIGNED,
    STATUS_UNSCHEDULABLE,
    STATUS_WAIT_GANG,
    score_all,
    score_cycle,
    greedy_assign,
)
from koordinator_tpu.solver.candidates import (  # noqa: F401
    CandidateOverflow,
    build_candidates,
    candidate_membership_mask,
    check_candidate_overflow,
    refresh_candidates,
    score_candidates,
    sparse_top_k,
)
from koordinator_tpu.solver.incremental import rescore_dirty  # noqa: F401
from koordinator_tpu.solver.topk import (  # noqa: F401
    masked_top_k,
    score_upper_bound,
)


# VMEM node limits of the dense Pallas cycle kernel, in node lanes (the
# padded node count), from AOT compiles at 10k pods for a described v5e
# (tests/test_chip_compile.py keeps the limits honest).  The kernel holds
# its [16, N] node rows in VMEM: 16,384 lanes compile and 32,768 do not;
# a caller extras tile adds a double-buffered [128, N] block, so with
# extras 8,192 compile and 16,384 do not.  Beyond a limit the cycle runs
# the XLA scan — chosen before launch, never by catching a failed compile.
DENSE_MAX_NODES = 16384
DENSE_MAX_NODES_EXTRAS = 8192


def dense_kernel_fits(n_nodes: int, has_extras: bool) -> bool:
    """Whether the dense Pallas kernel's node rows for ``n_nodes`` (the
    node bucket) fit VMEM on TPU; where they do not, the scan serves,
    with identical placements."""
    lanes = -(-int(n_nodes) // 128) * 128
    return lanes <= (DENSE_MAX_NODES_EXTRAS if has_extras else DENSE_MAX_NODES)


# The kernel's scoring multiplies clamped free capacity by MAX_NODE_SCORE
# (=100) in i32, so scored tensors need that much headroom below 2^31
# (model/resources.py documents the same ~20 TiB/node bound); quota rows
# are only added/compared, so they just need room for one more request.
_I32_SCORED_LIMIT = 2**31 // 100
_I32_QUOTA_LIMIT = 2**31 - 2**27


def i32_summary(alloc, requested, usage, pod_requests, pod_estimated,
                pod_valid, quota_runtime, quota_used, xp):
    """The few numbers the i32 gate needs, from the node, pod and quota
    tables as ``xp`` (numpy on the bridge's host mirrors, jax.numpy on
    a snapshot, where it is one fused transfer): a vector of
    [scored_max, quota_max, req_sum_max, slots_bound, est_max[R],
    usage_max[R], est_sum[R]].  ``slots_bound`` is how many pods one node
    can receive in a cycle, the largest free ``pods`` count of any node
    plus one, where every valid pod requests a ``pods`` slot (Fit then
    caps the slots), else -1."""
    from koordinator_tpu.model import resources as res

    slot = res.RESOURCE_INDEX[res.PODS]
    est = xp.abs(pod_estimated)
    takes_slot = xp.all(xp.where(pod_valid, pod_requests[:, slot] >= 1, True))
    free = xp.max(alloc[:, slot] - requested[:, slot]) if alloc.shape[0] else 0
    slots = xp.where(takes_slot, xp.maximum(free, 0) + 1, -1)

    def amax(a):
        return xp.max(xp.abs(a)) if a.size else xp.zeros((), xp.int64)

    head = [
        xp.max(xp.stack([amax(t) for t in (
            alloc, requested, usage, pod_requests, pod_estimated)])),
        xp.max(xp.stack([amax(quota_runtime), amax(quota_used)])),
        xp.max(xp.sum(xp.abs(pod_requests), axis=0)),
        slots,
    ]
    return xp.concatenate([
        xp.stack([xp.asarray(v, xp.int64) for v in head]),
        xp.max(est, axis=0, initial=0).astype(xp.int64),
        xp.max(xp.abs(usage), axis=0, initial=0).astype(xp.int64),
        xp.sum(est, axis=0).astype(xp.int64),
    ])


def check_i32_bounds(summary) -> bool:
    """``summary``: ``i32_summary``'s vector.

    Bounds the kernel's in-loop accumulators, not just its inputs.  The
    LoadAware term on one node sums its usage, the estimates assigned to
    it in the cycle and the scored pod's own: never more than every
    pod's estimate, and at most ``slots_bound`` estimates where Fit caps
    the pods a node takes.
    A quota's used row sums every assigned request in the cycle.  The
    worst-case cycle-end values must themselves fit i32."""
    import numpy as np

    v = np.asarray(summary, np.int64)
    scored_max, quota_max, req_sum_max, slots = (int(x) for x in v[:4])
    est_max, usage_max, est_sum = np.split(v[4:], 3)
    load = usage_max + (
        np.minimum(slots * est_max, est_sum) if slots >= 0 else est_sum
    )
    return (
        scored_max < _I32_SCORED_LIMIT
        and quota_max + req_sum_max < _I32_QUOTA_LIMIT
        and int(load.max()) < _I32_SCORED_LIMIT
    )


def pallas_inputs_fit_i32(snapshot) -> bool:
    """Node rows are bounded by design (MiB units) but quota rows are
    cluster-wide aggregates that can exceed i32 on very large clusters
    (> ~2 PiB memory).  Out-of-range inputs must take the i64 scan path —
    silent truncation would diverge placement with no error."""
    import jax.numpy as jnp
    import numpy as np

    n, p, q = snapshot.nodes, snapshot.pods, snapshot.quotas
    summary = i32_summary(
        n.allocatable, n.requested, n.usage, p.requests, p.estimated,
        p.valid, q.runtime, q.used, jnp,
    )
    return check_i32_bounds(np.asarray(summary))


def run_cycle(snapshot, cfg=None, extra_mask=None, extra_scores=None,
              i32_ok=None, has_gangs=None):
    """Backend-dispatched scheduling cycle.

    On TPU the dense-layout single-kernel Pallas cycle
    (solver/pallas_dense.py) runs the per-pod loop in VMEM; elsewhere,
    or when the input fails the kernel's i32 bounds or its node bucket
    exceeds the kernel's VMEM limit (``dense_kernel_fits``), the lax.scan
    path runs.  The choice is made before launch from what the input
    shows; a kernel that fails raises.  Both paths are bit-identical
    (tests/test_parity_fuzz.py).

    ``i32_ok``: callers that already know whether the snapshot fits the
    kernel's i32 arithmetic (e.g. the bridge server, which checks host-side
    numpy mirrors at Sync time) pass it to skip the per-cycle device check.

    Strict Coscheduling gangs (``constraints/gang.py``): a snapshot with
    a gang member (``has_gangs``, as ``i32_ok`` from the caller's
    mirrors, else read off the device) runs the dense kernel's gang
    variant, which rolls a rejected gang back as the scan does.

    Fused scoring terms (ISSUE 15): a ``cfg`` with term configs set
    materializes the registry's cellwise [P, N] tensors ONCE
    (solver/terms.py ``term_extras``, one async launch, no readback) and
    folds them into ``extra_mask``/``extra_scores`` — the scan and the
    Pallas kernel both consume the fused total through the seam they
    already had.  A terms-only extra needs NO device reduction for its
    magnitude bound: the registry's bound is a config property
    (``terms_upper_bound``).
    """
    import jax

    from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG

    if cfg is None:
        cfg = DEFAULT_CYCLE_CONFIG
    backend = jax.default_backend()
    caller_scores = extra_scores
    from koordinator_tpu.solver.terms import term_extras, terms_upper_bound

    t_scores, t_mask = term_extras(snapshot, cfg)
    if t_scores is not None:
        extra_scores = (
            t_scores if extra_scores is None else extra_scores + t_scores
        )
    if t_mask is not None:
        extra_mask = t_mask if extra_mask is None else extra_mask & t_mask
    has_extras = extra_mask is not None or extra_scores is not None
    extras_ok = True
    if extra_scores is not None:
        # magnitude bound for the kernel's i32 accumulation headroom.
        # Terms-only extras take the STATIC registry bound (no device
        # sync on the warm Assign path); caller extras still need the
        # one device reduction, and a composed total is bounded by the
        # sum of the two bounds.
        if caller_scores is None:
            scores_hi = terms_upper_bound(cfg)
        else:
            import jax.numpy as jnp

            scores_hi = int(jnp.max(jnp.abs(caller_scores)))
            if t_scores is not None:
                scores_hi += terms_upper_bound(cfg)
        extras_ok = scores_hi < 2**29
    if has_gangs is None:
        from koordinator_tpu.constraints.gang import has_gang_members

        has_gangs = has_gang_members(
            snapshot.pods.gang_id, snapshot.pods.valid,
            snapshot.gangs.min_member,
        )
    if (
        backend != "cpu" and extras_ok
        and dense_kernel_fits(snapshot.nodes.allocatable.shape[0], has_extras)
        and (i32_ok if i32_ok is not None else pallas_inputs_fit_i32(snapshot))
    ):
        from koordinator_tpu.solver.pallas_dense import greedy_assign_dense

        return greedy_assign_dense(
            snapshot, cfg, extra_mask=extra_mask, extra_scores=extra_scores,
            has_gangs=has_gangs,
        )
    return greedy_assign(snapshot, cfg, extra_mask=extra_mask, extra_scores=extra_scores)
