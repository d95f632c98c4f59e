"""Of a counter family's movement over the window, the percentage booked
under the listed label values. Nothing when the family did not move (an
older commit has no such family)."""
from lib.counters import total


def read(ctx, family, labels):
    moved = total(ctx["counters"], family)
    if not moved:
        return None
    return 100.0 * total(ctx["counters"], family, labels) / moved
