"""Pod shape kind `spread-by-service`: the entry's labels plus those of the
cycle's Service, which the factory draws from the seed once a closed-loop
cycle (`service_choice` `per-cycle`). The reference `default_provider` states
it."""
from __future__ import annotations

from lib.cluster import service_label

# keys an entry of this kind takes beyond kind, share, requests and labels
REQUIRED: dict = {}
OPTIONAL: dict = {}


def check(traffic: dict, n_services: int) -> None:
    if not traffic.get("service_choice") or not n_services:
        raise ValueError("spread-by-service pods need a service_choice "
                         "and a configuration with services")


def make(entry: dict, factory) -> tuple[dict, dict]:
    """(the fields of `api.types.Pod` the pod carries beyond name, namespace
    and containers; what its description states beyond cpu, mem, namespace,
    labels and kind)."""
    labels = dict(entry.get("labels") or {})
    labels.update(service_label(factory.cycle_service()))
    return {"labels": labels}, {}
