"""One general generator for every traffic mix: pod shapes and arrivals.

A mix is a data file (`traffic/<name>.json`). From it and the seed come the
pods of each backlog cycle, or the whole schedule of due times and pods of an
open-loop run, before the window opens. The same seed gives the same traffic.
The shapes follow `models/hollow.make_pods` and `bench.make_pods` (copied;
the originals are listed in PERF.md for a later PR to delete). It makes the
kinds the committed cells drive and the reference states, and no others.
"""
from __future__ import annotations

import random

from lib.cluster import service_label


class PodFactory:
    """Makes pods of the mix's shapes, and the plain description of each
    that the reference is given."""

    def __init__(self, traffic: dict, n_services: int, seed: int):
        self.shapes = traffic["pod_shapes"]
        self.n_services = n_services
        self.rng = random.Random(seed ^ 0x7AF1C)
        self._cum = []
        acc = 0.0
        for sh in self.shapes:
            acc += sh["share"]
            self._cum.append(acc)
        if any(sh["kind"] == "spread-by-service" for sh in self.shapes):
            if not traffic.get("service_choice") or not n_services:
                raise ValueError("spread-by-service pods need a service_choice "
                                 "and a configuration with services")
        self._cycle_service = None
        self._containers = {}
        self._descs = {}

    def new_cycle(self) -> None:
        """A closed-loop cycle begins: its Service is drawn."""
        if self.n_services:
            self._cycle_service = self.rng.randrange(self.n_services)

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
        labels = dict(sh.get("labels") or {})
        if kind == "spread-by-service":
            if self._cycle_service is None:
                self.new_cycle()
            labels.update(service_label(self._cycle_service))
        cont = self._containers.get((cpu, mem))
        if cont is None:
            cont = self._containers[(cpu, mem)] = (Container.make(
                name="c", requests={"cpu": cpu, "memory": mem}),)
        pod = Pod(name=name, namespace="default", labels=labels,
                  containers=cont)
        lab = tuple(sorted(labels.items()))
        dk = (cpu, mem, lab, kind)
        d = self._descs.get(dk)
        if d is None:
            d = self._descs[dk] = {"cpu": cpu, "mem": mem,
                                   "namespace": "default", "labels": lab,
                                   "kind": kind}
        return pod, d


def due_times(arrival: dict, seconds: float, seed: int) -> list[float]:
    """Offsets from the window's start at which arrivals are due, all inside
    [0, seconds): a Poisson process, exponential gaps at `rate_per_s`."""
    rng = random.Random(seed ^ 0xA881)
    rate = float(arrival["rate_per_s"])
    out = []
    t = rng.expovariate(rate)
    while t < seconds:
        out.append(t)
        t += rng.expovariate(rate)
    return out
