"""Step tracing — the utiltrace analog.

Mirrors k8s.io/utils/trace as used in the hot path
(core/generic_scheduler.go:185-246): named steps with timestamps, logged
only when the whole operation exceeds a threshold (the scheduler uses
100ms per cycle).
"""
from __future__ import annotations

import logging
import time

log = logging.getLogger("kubernetes_tpu")

SLOW_CYCLE_THRESHOLD = 0.1  # 100ms (generic_scheduler.go:186)


class Profiler:
    """Device-level profiling — the pprof-endpoint analog.

    The reference wires pprof HTTP handlers behind EnableProfiling
    (cmd/kube-scheduler/app/server.go:301-305, DebuggingConfiguration in
    apis/config/types.go:70); the TPU equivalent is a jax.profiler trace
    session writing TensorBoard/XPlane dumps (kernel timelines, HLO cost
    breakdowns, host<->device transfers) to a directory. Use either as a
    session (`start()`/`stop()`, the CLI flag path) or as a context manager
    around a region. While a session runs, every span the program opens
    through `obs.trace.span` is in the dump as an annotation of the same
    name: that is the one way to annotate a region."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._active = False

    def start(self) -> None:
        import jax
        if not self._active:
            jax.profiler.start_trace(self.log_dir)
            self._active = True

    def stop(self) -> None:
        import jax
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            log.warning("profiler trace written to %s", self.log_dir)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Trace:
    def __init__(self, name: str, threshold: float = SLOW_CYCLE_THRESHOLD):
        self.name = name
        self.threshold = threshold
        self.start = time.perf_counter()
        self.steps: list[tuple[str, float]] = []

    def step(self, msg: str) -> None:
        self.steps.append((msg, time.perf_counter()))

    def elapsed(self) -> float:
        return time.perf_counter() - self.start

    def log_if_long(self) -> bool:
        """Emit the step timeline when the operation was slow. Returns
        whether it logged."""
        total = self.elapsed()
        if total < self.threshold:
            return False
        lines = [f"Trace {self.name!r} (total {total * 1000:.1f}ms):"]
        prev = self.start
        for msg, t in self.steps:
            lines.append(f"  +{(t - prev) * 1000:.1f}ms {msg}")
            prev = t
        log.warning("\n".join(lines))
        return True

    def emit_spans(self, cat: str = "trace") -> None:
        """Fold the step timeline into the obs span ring: one parent span
        for the whole operation plus one child per step slice, so a slow
        cycle's breakdown shows up in /debug/traces, not only in the
        log."""
        from kubernetes_tpu.obs import trace as obs_trace
        end = time.perf_counter()
        obs_trace.add_span(self.name, self.start, end, cat=cat)
        prev = self.start
        for msg, t in self.steps:
            obs_trace.add_span(f"{self.name}: {msg}", prev, t, cat=cat,
                               args={"parent": self.name})
            prev = t
