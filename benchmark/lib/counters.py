"""Snapshots of the program's counters, read as deltas over the window.

Per-layer metrics may read the program's counters (`source:
program_counter`); no end-to-end metric does. The helpers follow
`chip_smoke.py`'s `family`/`delta` (copied)."""
from __future__ import annotations


def snapshot() -> dict:
    """{family name: {label values: value}} of every counter family in the
    program's registry."""
    from kubernetes_tpu import obs
    out = {}
    for fam in obs.REGISTRY.families():
        if isinstance(fam, obs.Counter):
            out[fam.name] = {tuple(k): c.value
                             for k, c in fam._children.items()}
    return out


def delta(after: dict, before: dict) -> dict:
    """The children that moved, and by how much."""
    out = {}
    for name, children in after.items():
        was = before.get(name, {})
        moved = {k: v - was.get(k, 0.0) for k, v in children.items()
                 if v - was.get(k, 0.0)}
        if moved:
            out[name] = moved
    return out


def total(deltas: dict, family: str, labels: list | None = None) -> float:
    """Sum of a family's moved children; `labels` keeps only children whose
    first label value is listed."""
    children = deltas.get(family, {})
    return float(sum(v for k, v in children.items()
                     if labels is None or (k and k[0] in labels)))
