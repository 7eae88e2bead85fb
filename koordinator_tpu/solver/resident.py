"""On-device sparse updates for the resident ClusterSnapshot.

The warm-cycle fast path (bridge/state.py) keeps the committed snapshot's
``jax.Array`` tensors alive across Syncs.  A warm Sync's sparse delta
frame is applied here as a jitted scatter straight into the resident
device buffer — the old buffer is DONATED (it is dead the moment the new
generation commits), so the update is in-place on backends that support
aliasing and the warm path never re-uploads the full table.

Exactness contract: a scatter of (idx, val) onto the resident array is
bit-identical to re-encoding the updated host mirror, because the flat
index space of the unpadded [N, ...] mirror embeds prefix-wise into the
row-padded [N_bucket, ...] device array (same trailing dims, row-major);
tests/test_resident_warm.py fuzzes this against cold re-encodes.

Compile economics: delta sizes vary per cycle, so (idx, val) are padded
to power-of-two buckets (pad slots carry an out-of-range index dropped
by ``mode="drop"``) — one compiled scatter per (shape, dtype, bucket)
instead of one per delta length.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from koordinator_tpu.model.snapshot import pad_bucket
from koordinator_tpu.obs import devprof


@devprof.boundary("solver.resident._scatter_flat")
@partial(jax.jit, donate_argnums=(0,))
def _scatter_flat(arr, idx, val):
    """arr.flat[idx] = val (OOB indices dropped), preserving arr's dtype.

    ``arr`` is donated: the pre-delta buffer backs the post-delta array
    where the backend supports input/output aliasing, so a warm update
    costs one small (idx, val) upload instead of a full-table transfer.
    """
    flat = arr.reshape(-1)
    flat = flat.at[idx].set(val.astype(arr.dtype), mode="drop")
    return flat.reshape(arr.shape)


@devprof.boundary("solver.resident._scatter_flat_sharded")
@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def _scatter_flat_sharded(arr, idx, val, *, mesh):
    """Shard-LOCAL scatter into a mesh-resident node tensor (ISSUE 7).

    ``arr`` is sharded along its leading (node) axis over the cluster
    mesh; ``idx``/``val`` replicate.  Each device rebases the global
    flat indices against its own shard's flat offset and scatters only
    the cells it owns — indices outside the shard (including the pad
    slots, which target ``arr.size`` globally) rebase out of the local
    range and are dropped.  NO collective runs: a delta for node *j*
    lands on the one device holding *j*'s rows, every other shard's
    program is a no-op scatter, and the donated pre-delta buffers alias
    in place per shard exactly like the single-chip path.

    Shardings are preserved (in_specs == out_specs), so the warm path
    never silently regathers the snapshot; one compiled program per
    (shape, dtype, bucket, mesh), same sticky-bucket economics as
    ``_scatter_flat``.
    """
    from koordinator_tpu.parallel.mesh import CLUSTER_AXIS
    from jax.sharding import PartitionSpec as P

    spec = P(CLUSTER_AXIS, *([None] * (arr.ndim - 1)))

    def body(a, idx, val):
        # contiguous leading-axis sharding: shard s owns the global flat
        # range [s * a.size, (s + 1) * a.size)
        start = jax.lax.axis_index(CLUSTER_AXIS).astype(idx.dtype) * a.size
        loc = idx - start
        owned = (loc >= 0) & (loc < a.size)
        loc = jnp.where(owned, loc, a.size)  # not-mine -> dropped
        flat = a.reshape(-1)
        flat = flat.at[loc].set(val.astype(a.dtype), mode="drop")
        return flat.reshape(a.shape)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, P(), P()), out_specs=spec,
        check_vma=False,
    )(arr, idx, val)


def apply_flat_delta(arr: "jax.Array", idx, val, mesh=None) -> "jax.Array":
    """Apply a sparse (flat-index, value) delta to a resident device array.

    ``idx``/``val`` are host arrays in the UNPADDED mirror's flat index
    space; because padding only appends rows, the same flat indices address
    the same cells in the row-padded resident array.  Returns the updated
    array; the input array is donated (dead) afterwards — callers must
    re-bind or drop their reference (the koordlint ``donation-safety``
    rule enforces this for module-local call sites; cross-module callers
    own the contract, see docs/ANALYSIS.md).

    Cross-THREAD donation contract (ISSUE 5): since the bridge daemon
    stopped serializing RPCs under one lock, a concurrent Score batch
    may hold a captured reference to the pre-delta snapshot.  Callers
    must launch this scatter under the device-dispatch lock
    (bridge/coalesce.py ``run_exclusive``) so the donation only
    invalidates buffers no in-flight launch can still read back; the
    scatter itself is a non-blocking async launch, which is what lets
    the next Sync's decode overlap it (docs/PIPELINE.md).

    ``mesh``: a cluster mesh (parallel/mesh.py) routes the scatter
    through the shard-local program — ``arr`` must be node-sharded over
    it; only the shard owning each index writes, nothing regathers.
    """
    idx = np.asarray(idx, np.int64)
    val = np.asarray(val, np.int64)
    bucket = pad_bucket(max(len(idx), 1))
    if len(idx) < bucket:
        # pad slots target arr.size, which mode="drop" discards
        pad = bucket - len(idx)
        idx = np.concatenate([idx, np.full(pad, arr.size, np.int64)])
        val = np.concatenate([val, np.zeros(pad, np.int64)])
    if mesh is not None and mesh.size > 1:
        scatter, kw = _scatter_flat_sharded, {"mesh": mesh}
    else:
        scatter, kw = _scatter_flat, {}
    return scatter(arr, jnp.asarray(idx), jnp.asarray(val), **kw)
