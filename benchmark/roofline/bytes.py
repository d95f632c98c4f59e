"""Bytes each jitted program has to move through HBM, from its launch shapes.

Each function is a floor: what any implementation of the same semantics has
to read and write at least, every plane once per launch. The chip's fast
memory holds these planes whole (a 16,384-row int64 plane is 128 KiB), so a
better kernel could keep them on chip for all the pods of a launch; counting
a plane once per pod would then be more than it moves, and the share could
pass 100%. So nothing is counted more than once per launch. With such a
floor these sequential programs read far below 1%: they are bound by the
latency of their per-pod steps, not by bandwidth, and the share says so.

Every model takes the launch's shapes by name: `rows` (node rows on one
chip, padded), `pods` (pods of the launch), `nodes` (the cluster's nodes).

Row widths (bytes per node row), from `TPUScheduler._NODE_FIELDS`: the int64
planes are 8 bytes (the TPU holds them as two u32), `valid` 1, `zone_id` 4.
"""
from __future__ import annotations

I64 = 8
# read by filter and score of a plain or spread pod
NODE_READ = (1                    # valid
             + 3 * I64            # alloc_cpu, alloc_mem, allowed_pods
             + 5 * I64            # req_cpu, req_mem, nz_cpu, nz_mem, pod_count
             + 4)                 # zone_id
# changed by placing pods, so written back once per launch
NODE_WRITE = 5 * I64              # req_cpu, req_mem, nz_cpu, nz_mem, pod_count
SPREAD_READ = I64                 # per-node count of the Service's pods
SCATTER_FIELDS = 1 + 4 + 12 * I64  # valid, zone_id and the twelve int64 planes


def pad_pow2(n: int, minimum: int = 1) -> int:
    p = max(1, minimum)
    while p < n:
        p *= 2
    return p


def schedule_batch_uniform(rows: int, pods: int, nodes: int = 0) -> int:
    """The uniform K-batch kernel: every node plane read once, the five
    changed planes written once, one selected row index out per pod."""
    return rows * (NODE_READ + NODE_WRITE) + pods * 4


def schedule_batch(rows: int, pods: int, nodes: int = 0) -> int:
    """The generic scan: as the uniform kernel, plus the spread-count plane
    read once and written once (it changes with every placement)."""
    return rows * (NODE_READ + NODE_WRITE + 2 * SPREAD_READ) + pods * 4


def scatter_rows(rows: int, pods: int, nodes: int) -> int:
    """The dirty-row scatter: a bucket of update rows read, the same rows of
    the resident planes written, and the row indices (int32) read. The bucket
    is the dirty rows (at most one per pod or node) padded to a power of two
    of at least 16."""
    bucket = pad_pow2(min(pods, nodes), 16)
    return bucket * (2 * SCATTER_FIELDS + 4)
