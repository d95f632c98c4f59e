"""Pods the client saw bound, per unit a counter family moved."""
from lib.counters import total


def read(ctx, family, labels=None):
    moved = total(ctx["counters"], family, labels)
    if not moved:
        return None
    return ctx["pods_bound"] / moved
