"""kubernetes_tpu — a TPU-native cluster-scheduling framework.

A from-scratch re-design of Kubernetes' kube-scheduler (reference:
kubernetes @ ~v1.15.0-alpha.3) for TPU hardware: the per-cycle NodeInfo
snapshot lives as a dense struct-of-arrays matrix in HBM, and the full
Filter/Score plugin suite runs as vmapped, jitted JAX kernels over all
nodes at once, with integer-exact score parity against the reference
algorithm (see `kubernetes_tpu.oracle` for the pure-Python referee).

Layout:
  api/        pruned Pod/Node/config data model (reference: pkg/apis, pkg/scheduler/api)
  oracle/     pure-Python semantic oracle — exact reference formulas, the parity referee
  ops/        JAX kernels: encoding, device snapshot, filter/score/select
  parallel/   multi-chip sharding of the node axis (mesh, per-shard top-k, all-gather)
  framework/  plugin framework: registry, extension points, cycle context
  cache/      scheduler cache: assume/confirm/expire, generations, snapshots
  queue/      scheduling queue: activeQ / backoffQ / unschedulableQ
  store/      in-memory versioned object store with list/watch (etcd+apiserver analog)
  models/     workload & cluster models for benchmarks (scheduler_perf / kubemark analog)
  perf/       parity cells for the tests and chip_smoke.py (the benchmark is benchmark/run.py)
  utils/      heap, clock, backoff helpers
"""

__version__ = "0.1.0"

# NOTE: jax is imported (and jax_enable_x64 switched on — reference resource
# math is int64) by `kubernetes_tpu.ops`, the first layer that touches the
# device. The api/oracle/cache/queue/store layers stay pure Python.
