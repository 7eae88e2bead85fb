"""AOT compiles of the served path's device programs for a described v5e.

Nothing runs: each program is lowered and compiled for one chip of a
described, unattached ``v5e:2x2`` topology, which raises what the chip's
compiler would raise (an unsupported 64-bit type inside a kernel, more
VMEM than a kernel may use, a program that does not fit HBM).  Shapes
are the served buckets of BASELINE.json config 4: 10,000 pods in the
16,384 pod bucket, 2,000 nodes in the 2,048 node bucket (a smaller node
bucket holds five pods a node).  The dense
kernel is also compiled at k8s5k's 8,192 node lanes, at the gang cell's
512 x 8,192 buckets, at the node-bucket limits run_cycle routes by
(``solver.DENSE_MAX_NODES``), and refused just past them; each compile
checks that the kernel takes the valid pod count, the operand that
bounds the rows it runs.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import pytest

from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG, CycleConfig
from koordinator_tpu.harness import generators

POD_BUCKET, NODE_BUCKET = 16384, 2048
# score_cycle's i64 [P, N] intermediates at the served buckets: 3.53e9
# bytes of temp measured with jax/libtpu 0.9.0 / 0.0.34
SCORE_TEMP_CEILING = 3.6e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler would otherwise log under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip:
    # keep these out of the persistent cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, node_bucket=NODE_BUCKET, pod_bucket=POD_BUCKET):
    nodes = min(2_000, node_bucket)
    snap = generators.quota_colocation_snapshot(
        pods=nodes * 5, nodes=nodes,
        node_bucket=node_bucket, pod_bucket=pod_bucket,
    )[0]
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        snap,
    )


def _dense(one_chip, node_bucket, extras, gangs=False, pod_bucket=POD_BUCKET):
    from koordinator_tpu.solver.pallas_dense import _greedy_assign_dense

    spec = _spec(one_chip, node_bucket, pod_bucket)
    cells = (pod_bucket, node_bucket)
    mask = scores = None
    if extras:
        mask = jax.ShapeDtypeStruct(cells, bool, sharding=one_chip)
        scores = jax.ShapeDtypeStruct(cells, "int64", sharding=one_chip)
    return _greedy_assign_dense.__wrapped__.lower(
        spec, DEFAULT_CYCLE_CONFIG, False, mask, scores, gangs
    ).compile()


def _takes_valid_count(compiled) -> bool:
    """Whether the kernel's custom call takes the valid pod count: an
    ``s32[1]`` scalar-prefetch operand after the two ``[P]`` ones (queue
    ids, prod flags)."""
    return re.search(
        r"custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
        r"\{(s32\[\d+\]\{0\}, ){2}s32\[1\]\{0\}",
        compiled.as_text(),
    ) is not None


@pytest.mark.parametrize("node_bucket", [NODE_BUCKET, 8192])
def test_dense_kernel_served_buckets(one_chip, node_bucket):
    """colo2k.batch's 2,048 and k8s5k.batch's 8,192 node lanes."""
    compiled = _dense(one_chip, node_bucket, extras=False)
    assert _takes_valid_count(compiled)


@pytest.mark.parametrize("extras", [False, True])
def test_dense_kernel_at_node_limit(one_chip, extras):
    from koordinator_tpu import solver

    limit = solver.DENSE_MAX_NODES_EXTRAS if extras else solver.DENSE_MAX_NODES
    compiled = _dense(one_chip, limit, extras)
    assert _takes_valid_count(compiled)


@pytest.mark.parametrize("node_bucket,pod_bucket", [(512, 8192),
                                                    (16384, POD_BUCKET)])
def test_dense_gang_kernel_compiles(one_chip, node_bucket, pod_bucket):
    """The Strict-gang variant (its checkpoint scratch and scalar gate)
    at the gang cell's buckets and at the dense node limit."""
    compiled = _dense(one_chip, node_bucket, extras=False, gangs=True,
                      pod_bucket=pod_bucket)
    assert _takes_valid_count(compiled)


def test_dense_kernel_refused_past_node_limit(one_chip):
    from koordinator_tpu import solver

    with pytest.raises(Exception, match="vmem"):
        _dense(one_chip, 2 * solver.DENSE_MAX_NODES, extras=False)


def test_wide_wave_kernel_served_buckets(one_chip):
    from koordinator_tpu.solver.pallas_cycle import _greedy_assign_pallas

    compiled = _greedy_assign_pallas.__wrapped__.lower(
        _spec(one_chip), CycleConfig(wave=32, top_m=4)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_score_cycle_served_buckets(one_chip):
    from koordinator_tpu.solver.greedy import score_cycle

    compiled = score_cycle.__wrapped__.lower(
        _spec(one_chip), cfg=DEFAULT_CYCLE_CONFIG
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < SCORE_TEMP_CEILING, temp


def test_incremental_rescore_served_buckets(one_chip):
    """The warm Score engine at the smoke's dirty buckets (48 usage
    deltas -> 64 dirty columns, 300 churned pods -> 512 dirty rows):
    a column scatter here was refused for scoped VMEM on the chip."""
    import jax.numpy as jnp

    from koordinator_tpu.solver.incremental import _rescore

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cells = (POD_BUCKET, NODE_BUCKET)
    _rescore.__wrapped__.lower(
        _spec(one_chip), arg(cells, jnp.int64), arg(cells, bool),
        arg((64,), jnp.int64), arg((512,), jnp.int64),
        cfg=DEFAULT_CYCLE_CONFIG,
    ).compile()


def test_shard_wave_cycle_on_four_chips(topo):
    """The mesh-resident Assign (``--mesh 4``): the round-based wave
    cycle over a 4-chip cluster mesh, node tables sharded.  Its in-wave
    deltas were an i64 einsum, which the TPU's X64 rewrite refuses."""
    from koordinator_tpu.parallel.mesh import (
        cluster_mesh,
        snapshot_shardings,
    )
    from koordinator_tpu.parallel.shard_assign import _assign_waves

    mesh = cluster_mesh(topo.devices[:4])
    snap = generators.quota_colocation_snapshot(
        pods=10_000, nodes=2_000,
        node_bucket=NODE_BUCKET, pod_bucket=POD_BUCKET,
    )[0]
    spec = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        snap, snapshot_shardings(snap, mesh),
    )
    compiled = _assign_waves.__wrapped__.lower(
        spec, None, None, cfg=DEFAULT_CYCLE_CONFIG, mesh=mesh,
        has_mask=False, has_scores=False, wave=32,
        top_m=DEFAULT_CYCLE_CONFIG.top_m,
    ).compile()
    assert "all-gather" in compiled.as_text()

