"""Explicit backpressure for the serving mode — admission shed with 429.

Real apiservers shed load instead of queueing unboundedly (priority &
fairness, the eviction subresource's 429 + Retry-After); this module is
that contract for the serving pipeline. A `BackpressureGate` attaches to
the store's pod-create path (`Store.admission_gate`; the apiserver maps
the refusal to HTTP 429 with Retry-After) and sheds creates when the
activeQ depth watermark is exceeded: pending pods the scheduler has not
popped yet are the direct measure of queue wait eating the startup SLO.

The suggested Retry-After scales with how far over the watermark the
queue is (a deeper queue needs a longer back-off to drain), bounded by
`retry_after_max`. Shedding is observable: `admission_rejected_total
{reason}` counts sheds by cause and the `serve_activeq_depth` gauge
reads the live value at scrape time.

Rejection evicts the pod's lifecycle-ledger record (the round-16 bugfix):
first-stamp-wins would otherwise carry a shed attempt's stamp into the
readmitted pod and bill the client's backoff as startup latency.
"""
from __future__ import annotations

from typing import Callable

from kubernetes_tpu import chaos, obs
from kubernetes_tpu.store.store import BackpressureError

ADMISSION_REJECTED = obs.counter(
    "admission_rejected_total",
    "Pod creates shed by the serving backpressure gate, by reason: "
    "queue-depth (activeQ over the watermark), injected (the chaos "
    "serve.shed seam fired). "
    "Every shed answered 429 + Retry-After; the write never landed.",
    ("reason",))

_ACTIVEQ_DEPTH = obs.gauge(
    "serve_activeq_depth",
    "Live activeQ depth the serving admission gate keys on (the most "
    "recently attached gate wins the gauge).")
_SHED_STATE = obs.gauge(
    "serve_backpressure_active",
    "1 while the most recently attached serving gate is shedding "
    "(activeQ depth at/over the watermark), else 0.")


class BackpressureGate:
    """Admission gate keyed on activeQ depth.

    `depth_fn` returns the live activeQ depth (the scheduler queue's
    `active_depth`; the ServeLoop wires its own). `admit(pod)`
    raises `BackpressureError` carrying the suggested Retry-After, after
    evicting the pod's ledger record; it is called by `Store.create`
    under no store lock (the gate reads are lock-free snapshots — an
    admit racing a pop may let one extra pod in, which the NEXT create
    sheds; the watermark is flow control, not an invariant)."""

    def __init__(self, depth_fn: Callable[[], int],
                 max_depth: int = 50_000,
                 retry_after_base: float = 0.05,
                 retry_after_max: float = 2.0):
        self.depth_fn = depth_fn
        self.max_depth = int(max_depth)
        self.retry_after_base = float(retry_after_base)
        self.retry_after_max = float(retry_after_max)
        self.rejected = 0          # total sheds through THIS gate
        self.admitted = 0
        _ACTIVEQ_DEPTH.set_function(lambda: float(self.depth_fn()))
        _SHED_STATE.set_function(
            lambda: 1.0 if self.depth_fn() >= self.max_depth else 0.0)

    def suggest_retry_after(self, depth: int) -> float:
        """Backoff suggestion scaled by overload: at the watermark the
        base applies; k watermarks deep suggests ~k x base (a deeper
        queue needs proportionally longer to drain), capped."""
        over = max(1.0, depth / max(self.max_depth, 1))
        return min(self.retry_after_max, self.retry_after_base * over)

    def _shed(self, pod, reason: str, message: str) -> None:
        self.rejected += 1
        ADMISSION_REJECTED.labels(reason).inc()
        # the round-16 ledger bugfix: a shed pod's record must not
        # survive into its readmitted life with the stale first stamp
        from kubernetes_tpu.obs.ledger import LEDGER
        LEDGER.evict(pod.key)
        raise BackpressureError(
            message, retry_after=self.suggest_retry_after(self.depth_fn()))

    def admit(self, pod) -> None:
        """Raise BackpressureError to shed `pod`'s create; return to
        admit. Checked at the store/apiserver admission surface BEFORE
        anything is written."""
        if chaos.take("serve.shed"):
            self._shed(pod, "injected",
                       f"{pod.key}: chaos-injected admission shed")
        depth = self.depth_fn()
        if depth >= self.max_depth:
            self._shed(pod, "queue-depth",
                       f"{pod.key}: activeQ depth {depth} >= "
                       f"watermark {self.max_depth}")
        self.admitted += 1

    def admit_many(self, pods) -> tuple:
        """ONE gate evaluation for a whole create_many batch: returns
        (n_admitted, retry_after) where pods[:n_admitted] are admitted
        and the TAIL is shed (retry_after is None when nothing shed).

        Semantics mirror per-pod admits exactly: each serial create
        grows the informer backlog by one before the next gate read, so
        pod i of the batch is evaluated against depth base+i — the depth
        watermark therefore sheds a TAIL, never a middle. A chaos
        serve.shed draw mid-batch sheds from that pod on (flow control
        errs toward shedding — the seam is an opt-in chaos path, and shed
        arrivals re-admit).
        Ledger records of shed pods are evicted in one batch, exactly
        like the per-pod _shed path."""
        n = len(pods)
        base = self.depth_fn()
        accepted = 0
        reason = None
        for pod in pods:
            if chaos.take("serve.shed"):
                reason = "injected"
                break
            if base + accepted >= self.max_depth:
                reason = "queue-depth"
                break
            accepted += 1
        self.admitted += accepted
        if accepted == n:
            return n, None
        shed = pods[accepted:]
        self.rejected += len(shed)
        ADMISSION_REJECTED.labels(reason).inc(len(shed))
        from kubernetes_tpu.obs.ledger import LEDGER
        LEDGER.evict_many([p.key for p in shed])
        return accepted, self.suggest_retry_after(base + accepted)

    def debug_state(self) -> dict:
        return {
            "max_depth": self.max_depth,
            "depth": int(self.depth_fn()),
            "admitted": self.admitted,
            "rejected": self.rejected,
        }

