"""Described-chip compiles of the programs each cell runs, at its buckets.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_v5e.py

Compiles for one chip of a described, unattached v5e (no chip is used)
and prints each program's ``memory_analysis()``: the dense Assign kernel
at 16,384 pods x 2,048 nodes (colo2k.batch) and x 8,192 nodes
(k8s5k.batch), and the Score programs at 8 pods x 8,192 nodes
(k8s5k.prescore: the one-pod table pads to 8 rows).  A refusal here is
what the chip's compiler would refuse.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import koordinator_tpu  # noqa: F401
    from koordinator_tpu.config import DEFAULT_CYCLE_CONFIG as CFG
    from koordinator_tpu.harness import generators
    from koordinator_tpu.solver.greedy import score_cycle
    from koordinator_tpu.solver.incremental import _rescore
    from koordinator_tpu.solver.pallas_dense import _greedy_assign_dense

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(pods, nodes, pod_bucket, node_bucket):
        snap = generators.quota_colocation_snapshot(
            pods=pods, nodes=nodes, node_bucket=node_bucket,
            pod_bucket=pod_bucket)[0]
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            snap)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(name, compiled):
        m = compiled.memory_analysis()
        kernel = "tpu_custom_call" in compiled.as_text()
        print(f"{name}: argument={m.argument_size_in_bytes} "
              f"output={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
              f"generated_code={m.generated_code_size_in_bytes} "
              f"pallas_kernel={kernel}", flush=True)

    for nodes, nb in ((2000, 2048), (5000, 8192)):
        report(f"dense_assign 16384x{nb}",
               _greedy_assign_dense.__wrapped__.lower(
                   spec(10000, nodes, 16384, nb), CFG, False, None, None
               ).compile())
    s = spec(1, 5000, 8, 8192)
    report("score_cycle 8x8192",
           score_cycle.__wrapped__.lower(s, cfg=CFG).compile())
    report("incremental_rescore 8x8192 (8 dirty nodes, 8 dirty pods)",
           _rescore.__wrapped__.lower(
               s, arg((8, 8192), jnp.int64), arg((8, 8192), bool),
               arg((8,), jnp.int64), arg((8,), jnp.int64), cfg=CFG,
           ).compile())


if __name__ == "__main__":
    main()
