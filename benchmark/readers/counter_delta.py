"""A counter family's movement over the window, undivided. Nothing when
the program has no such family (an older commit): 0 means it did not move."""
from lib.counters import total


def read(ctx, family, labels=None):
    from kubernetes_tpu import obs
    if not any(f.name == family for f in obs.REGISTRY.families()):
        return None
    return total(ctx["counters"], family, labels)
