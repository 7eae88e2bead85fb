"""koordinator_tpu — a TPU-native batched scheduling framework.

A ground-up rebuild of the capabilities of koordinator (QoS-based co-location
scheduling; reference at /root/reference) around a JAX/XLA core: the
scheduler-framework Score phase (NodeResourcesFit, LoadAwareScheduling,
NodeNUMAResource) is computed as one dense ``pods x nodes`` cost tensor on
TPU, with Coscheduling gang constraints and ElasticQuota hierarchical caps
encoded as masks, and a batched assignment solver replacing the per-pod
sequential scheduling cycle.

Design notes
------------
* All scoring arithmetic is exact int64 integer math so that score output is
  bit-identical with the reference's Go scorers (which use int64 division,
  e.g. ``leastRequestedScore`` at
  reference ``pkg/scheduler/plugins/loadaware/load_aware.go:388``).
  This requires ``jax_enable_x64``; importing this package enables it.
* Shapes are static: snapshots are padded to shape buckets so that XLA
  compiles each bucket once (see ``koordinator_tpu.model.snapshot``).
* Multi-chip scale-out shards the pod axis (data-parallel analog) and the
  node axis (model-parallel analog) of the cost tensor over a
  ``jax.sharding.Mesh`` (see ``koordinator_tpu.parallel.mesh``).
"""

import os

import jax

# Exact int64 score parity with the reference's Go integer math requires x64.
# Elementwise i64 is emulated on TPU but the score tensors are small compared
# to HBM bandwidth, so this costs little; the f32 fast path in ops/ avoids it
# where parity is not required.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: the cycle kernels take 10-20 s to
# compile per shape bucket, and a restarted scheduler must be ready at
# informer-sync speed (reference analog:
# cmd/koord-scheduler/app/server.go:206-220).  One rule, applied here and
# nowhere else: where JAX_COMPILATION_CACHE_DIR is set, JAX reads it and
# this package sets nothing; otherwise the cache lives at one fixed,
# gitignored path inside the checkout — never under ~, a temp name or a
# PID, since a cache directory that moves never hits.
XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)

__version__ = "0.1.0"
