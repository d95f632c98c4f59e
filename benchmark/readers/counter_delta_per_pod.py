"""A counter family's movement over the window, per `per` pods bound."""
from lib.counters import total


def read(ctx, family, labels=None, per=1):
    if not ctx["pods_bound"]:
        return None
    return total(ctx["counters"], family, labels) * per / ctx["pods_bound"]
