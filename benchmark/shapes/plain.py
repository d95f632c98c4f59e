"""Pod shape kind `plain`: the entry's fixed labels and requests, nothing
drawn. The reference `default_provider` states it."""
from __future__ import annotations

# keys an entry of this kind takes beyond kind, share, requests and labels
REQUIRED: dict = {}
OPTIONAL: dict = {}


def check(traffic: dict, n_services: int) -> None:
    """Any mix on any configuration carries plain pods."""


def make(entry: dict, factory) -> tuple[dict, dict]:
    """(the fields of `api.types.Pod` the pod carries beyond name, namespace
    and containers; what its description states beyond cpu, mem, namespace,
    labels and kind)."""
    return {"labels": dict(entry.get("labels") or {})}, {}
