"""Bytes of the generic scan when it ships its NodeTree orders as
permutations (`rotate=True, rotate_pos=False` of `ops/kernels.py`
`_batch_core`: a rotating tree under a truncated walk).

A floor like its neighbours in `roofline/bytes.py`, every plane once per
launch: `schedule_batch`'s planes, plus the two order tables `perms` and
`inv_perms` ([ORDERS, rows] int32) and `oid_seq`, the order id of each cycle
(int32 a pod). A step reads one row of each table, and the tables fit the
chip's fast memory, so a row per step would count more than has to move.
"""
from __future__ import annotations

from roofline.bytes import schedule_batch

I32 = 4
# rows of an order table in a cluster of three zones: the axis order and one
# per zone the tree's cursor can start from, which `_generic_rotation` pads
# to a power of two of at least 4
ORDERS = 4


def schedule_batch_rotation(rows: int, pods: int, nodes: int = 0) -> int:
    return (schedule_batch(rows, pods, nodes)
            + 2 * ORDERS * rows * I32 + pods * I32)
