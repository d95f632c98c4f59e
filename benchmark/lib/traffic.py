"""One general generator for every traffic mix: pod shapes and arrivals.

A mix is a data file (`traffic/<name>.json`). From it and the seed come the
pods of each backlog cycle, or the whole schedule of due times and pods of an
open-loop run, before the window opens. The same seed gives the same traffic.
What one pod shape kind puts on a pod is `shapes/<kind>.py`, and what one
arrival process makes of a rate is `arrivals/<process>.py`, each found by the
name the mix gives (`spec.load_module`): a new kind is a new file.
"""
from __future__ import annotations

import random

from lib import spec


class PodFactory:
    """Makes pods of the mix's shapes, and the plain description of each
    that the reference is given."""

    def __init__(self, traffic: dict, n_services: int, seed: int,
                 root: str = spec.ROOT):
        self.shapes = traffic["pod_shapes"]
        self.n_services = n_services
        self.rng = random.Random(seed ^ 0x7AF1C)
        self._cum = []
        self._kinds = {}
        acc = 0.0
        for sh in self.shapes:
            acc += sh["share"]
            self._cum.append(acc)
            kind = self._kinds[sh["kind"]] = spec.load_module(
                "shapes", sh["kind"], root)
            kind.check(traffic, n_services)
        self._cycle_service = None
        self._containers = {}
        self._descs = {}

    def new_cycle(self) -> None:
        """A closed-loop cycle begins: its Service is drawn."""
        if self.n_services:
            self._cycle_service = self.rng.randrange(self.n_services)

    def cycle_service(self) -> int:
        """The cycle's Service, drawn now where no cycle was begun."""
        if self._cycle_service is None:
            self.new_cycle()
        return self._cycle_service

    def _shape(self) -> dict:
        if len(self.shapes) == 1:
            return self.shapes[0]
        x = self.rng.random()
        for sh, c in zip(self.shapes, self._cum):
            if x < c:
                return sh
        return self.shapes[-1]

    def make(self, name: str):
        """(Pod, description). Descriptions of equal pods are one object."""
        from kubernetes_tpu.api.types import Container, Pod
        sh = self._shape()
        cpu = sh["requests"]["cpu_milli"]
        mem = sh["requests"]["memory_bytes"]
        kind = sh["kind"]
        fields, stated = self._kinds[kind].make(sh, self)
        cont = self._containers.get((cpu, mem))
        if cont is None:
            cont = self._containers[(cpu, mem)] = (Container.make(
                name="c", requests={"cpu": cpu, "memory": mem}),)
        fields.setdefault("containers", cont)   # a kind with ports has its own
        pod = Pod(name=name, namespace="default", **fields)
        lab = tuple(sorted(fields["labels"].items()))
        dk = (cpu, mem, lab, kind, *sorted(stated.items())) if stated \
            else (cpu, mem, lab, kind)
        d = self._descs.get(dk)
        if d is None:
            d = self._descs[dk] = {"cpu": cpu, "mem": mem,
                                   "namespace": "default", "labels": lab,
                                   "kind": kind, **stated}
        return pod, d


def due_times(arrival: dict, seconds: float, seed: int,
              root: str = spec.ROOT) -> list[float]:
    """Offsets from the window's start at which arrivals are due, all inside
    [0, seconds), as the mix's arrival process makes them from the seed."""
    process = spec.load_module("arrivals", arrival["process"], root)
    return process.due_times(arrival, seconds, seed)
