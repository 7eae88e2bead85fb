"""Coscheduling gang (PodGroup) helpers: Strict gangs in the batched cycle.

Koordinator's gang scheduling (``gang.scheduling.koordinator.sh/
min-available`` and ``/mode``; coscheduling ``core/core.go``) in its
default mode, Strict.  The wire carries no mode (``GangTable`` has only
``min_member``), so every gang is Strict; NonStrict is not supported.

* PreFilter (``core.go:241-246``): a gang whose valid members number
  fewer than its min member never enters the cycle.  ``strict_gangs``
  folds that into the pods' validity before the engine runs.
* In the cycle (PostFilter ``rejectGangGroupById`` and Unreserve): the
  engines (``solver/greedy.py`` scan, ``solver/pallas_dense.py``) visit
  a gang's members adjacently (``solver.greedy.queue_order``).  A member
  that finds no feasible node while its gang has fewer than min member
  placed rejects the gang: every placement the gang made in the cycle
  is undone (node requested, assigned estimates, quota used), its
  remaining members are skipped, and later pods see the freed capacity.
  A gang that has reached min member is satisfied and is not rejected.
* ``strict_post_pass`` writes the reply: every member of a rejected gang
  gets assignment -1; members placed and then undone get WAIT_GANG (so
  ``gang_manager.apply_cycle_result`` releases them), the failing and
  skipped members UNSCHEDULABLE.

``gang_satisfaction`` is the Permit count (``core.go:308``) the engines
without a rollback (the multi-chip shard path) still applies after its cycle;
no served snapshot with gangs reaches them (``solver.run_cycle``).
"""

from __future__ import annotations

import jax.numpy as jnp


def gang_satisfaction(
    assignment: jnp.ndarray,  # i32[P] node or -1
    pod_valid: jnp.ndarray,  # bool[P]
    gang_id: jnp.ndarray,  # i32[P], -1 = no gang
    min_member: jnp.ndarray,  # i32[G]
):
    """Returns (gang_satisfied bool[G], pod_gang_ok bool[P]).

    A pod with no gang is always ok; a gang is satisfied when its number of
    assigned members reaches minMember (Permit-stage check, core.go:308).
    """
    G = min_member.shape[0]
    assigned = (assignment >= 0) & pod_valid
    slot = jnp.where(gang_id >= 0, gang_id, G)
    counts = jnp.zeros((G + 1,), jnp.int32).at[slot].add(assigned.astype(jnp.int32))
    satisfied = counts[:G] >= min_member
    pod_ok = jnp.where(gang_id >= 0, satisfied[jnp.maximum(gang_id, 0)], True)
    return satisfied, pod_ok


def strict_gangs(gang_id, valid, min_member):
    """The cycle's view of the gangs, PreFilter applied.

    Returns ``(gang, valid, need)``, each [P]: the pod's gang id, -1 for
    a pod the cycle treats as gang-free (no gang, an id outside the
    table, a gang of min member <= 0, which can never be rejected, or an
    invalid pod); its validity with the members of a gang of too few
    valid members removed; its gang's min member (0 where gang-free).
    """
    G = min_member.shape[0]
    if G == 0:
        return jnp.full_like(gang_id, -1), valid, jnp.zeros_like(gang_id)
    known = (gang_id >= 0) & (gang_id < G)
    need = jnp.where(
        known, min_member[jnp.clip(gang_id, 0, G - 1)], 0
    ).astype(jnp.int32)
    tracked = known & (need > 0)
    slot = jnp.where(tracked, gang_id, G)
    members = jnp.zeros((G + 1,), jnp.int32).at[slot].add(valid.astype(jnp.int32))
    valid = valid & ~(tracked & (members[slot] < need))
    gang = jnp.where(tracked & valid, gang_id, -1).astype(jnp.int32)
    return gang, valid, jnp.where(gang >= 0, need, 0)


def has_gang_members(gang_id, valid, min_member, xp=jnp) -> bool:
    """Whether any valid pod is a member of a gang of the table with min
    member > 0: the snapshot then runs the engines with the Strict
    rollback.  ``xp`` is numpy on the bridge's mirrors
    (``ResidentState.has_gangs``), jax.numpy on a snapshot (one device
    reduction)."""
    G = len(min_member)
    if G == 0 or gang_id is None:
        return False
    known = (gang_id >= 0) & (gang_id < G)
    need = xp.asarray(min_member)[xp.clip(gang_id, 0, G - 1)]
    return bool(xp.any(valid & known & (need > 0)))


def gang_opens(gang_sorted):
    """bool[P] in queue order: the pod is its gang's first member (the
    engines checkpoint their state there)."""
    prev = jnp.concatenate([jnp.full((1,), -1, gang_sorted.dtype), gang_sorted[:-1]])
    return (gang_sorted >= 0) & (gang_sorted != prev)


def strict_post_pass(chosen, gang, need, num_gangs: int):
    """``chosen`` i32[P]: each pod's node as the engine placed it (-1
    none), before any gang was undone; ``gang``, ``need`` from
    ``strict_gangs`` over a table of ``num_gangs``.  Returns
    (assignment, status) with the reply statuses of ``solver.greedy``:
    a gang whose placed members are fewer than its min member was
    rejected (a satisfied gang never loses a member), so its members get
    -1, WAIT_GANG where the engine had placed them, else UNSCHEDULABLE."""
    from koordinator_tpu.solver.greedy import (
        STATUS_ASSIGNED,
        STATUS_UNSCHEDULABLE,
        STATUS_WAIT_GANG,
    )

    placed = chosen >= 0
    slot = jnp.where(gang >= 0, gang, num_gangs)
    counts = jnp.zeros((num_gangs + 1,), jnp.int32).at[slot].add(
        placed.astype(jnp.int32)
    )
    rejected = (gang >= 0) & (counts[slot] < need)
    assignment = jnp.where(rejected, -1, chosen).astype(jnp.int32)
    status = jnp.where(
        rejected & placed,
        STATUS_WAIT_GANG,
        jnp.where(assignment >= 0, STATUS_ASSIGNED, STATUS_UNSCHEDULABLE),
    )
    return assignment, status.astype(jnp.int32)
