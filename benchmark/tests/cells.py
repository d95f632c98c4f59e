"""Which cell reports which metric, asked of `spec.metrics_for`: the rule
the harness itself goes by (a per-layer metric without a `workloads` list is
every cell's that reports the end-to-end metric it moves). The spec tests ask
through these, so a later cell or metric breaks none of them."""
from lib import spec


def names(bench: dict, cell: str, group: str = "per_layer") -> list:
    return [m["name"] for m in
            spec.metrics_for(bench, spec.find_cell(bench, cell), group)]


def reports(bench: dict, cell: str, metric: str) -> bool:
    return metric in names(bench, cell) + names(bench, cell, "end_to_end")


def reporting(bench: dict, metric: str) -> list:
    """The cells that report `metric`, in `workloads` order."""
    return [w["name"] for w in bench["workloads"]
            if reports(bench, w["name"], metric)]
