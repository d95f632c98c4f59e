"""The benchmark's client: it submits pods and watches them get bound.

Everything end to end is read here, from the client's side of the store's
public verbs: `create_many`, `delete_many` and one `watch(PODS)` of its own.
The client keeps the whole stream of what its watch showed (binds and
deletes, in commit order) as compact parallel lists; the plain reference
replays that stream after the window. It reads no counter, ledger or field of
the program.
"""
from __future__ import annotations

import contextlib
import time

ADD, BIND, DELETE = 0, 1, 2


class Client:
    def __init__(self, store, tracing: bool):
        from kubernetes_tpu.store.store import PODS
        self.store = store
        self.kind = PODS
        self.watch = store.watch(PODS)
        self.tracing = tracing
        # the pods this client created: id = position
        self.keys: list[str] = []
        self.descs: list[dict] = []
        self.id_of: dict[str, int] = {}
        self.bind_seen_at: list[float] = []      # perf_counter, or 0.0
        self.bind_count: list[int] = []
        # what the watch showed, in order
        self.log_kind: list[int] = []
        self.log_pod: list[int] = []
        self.log_node: list = []
        # seconds the client itself spent, by activity
        self.spent = {"make": 0.0, "create": 0.0, "watch_drain": 0.0,
                      "reap": 0.0}
        self.unknown_events = 0

    def span(self, name: str):
        """A span in the profiler's own trace around one of the benchmark's
        calls, when a trace is being taken."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- submitting --------------------------------------------------------
    def register(self, pod, desc: dict) -> int:
        pid = len(self.keys)
        self.keys.append(pod.key)
        self.descs.append(desc)
        self.id_of[pod.key] = pid
        self.bind_seen_at.append(0.0)
        self.bind_count.append(0)
        return pid

    def create(self, pods: list) -> tuple[int, float | None]:
        """One `create_many`. Returns (accepted, retry_after): a 429 admits a
        prefix and names the back-off for the rest."""
        from kubernetes_tpu.store.store import BackpressureError
        t0 = time.perf_counter()
        try:
            with self.span("client.create"):
                self.store.create_many(self.kind, pods)
            return len(pods), None
        except BackpressureError as e:
            k = max(0, min(int(getattr(e, "accepted", 0)), len(pods)))
            return k, float(e.retry_after)
        finally:
            self.spent["create"] += time.perf_counter() - t0

    def delete(self, keys: list) -> int:
        t0 = time.perf_counter()
        with self.span("client.reap"):
            gone = self.store.delete_many(self.kind, keys)
        self.spent["reap"] += time.perf_counter() - t0
        return len(gone)

    # -- watching ----------------------------------------------------------
    def drain(self) -> int:
        """Take everything the watch has. Every bind it shows is stamped with
        the time this drain returned: that is when the client knew. Returns
        the number of binds seen."""
        from kubernetes_tpu.store.store import ADDED, DELETED, MODIFIED
        t0 = time.perf_counter()
        with self.span("client.watch_drain"):
            events = self.watch.drain()
            now = time.perf_counter()
            binds = 0
            id_of = self.id_of
            lk, lp, ln = self.log_kind, self.log_pod, self.log_node
            for ev in events:
                obj = ev.obj
                pid = id_of.get(obj.key)
                if pid is None:
                    self.unknown_events += 1
                    continue
                et = ev.type
                if et == MODIFIED:
                    if obj.node_name:
                        lk.append(BIND)
                        lp.append(pid)
                        ln.append(obj.node_name)
                        self.bind_count[pid] += 1
                        if self.bind_seen_at[pid] == 0.0:
                            self.bind_seen_at[pid] = now
                        binds += 1
                elif et == DELETED:
                    lk.append(DELETE)
                    lp.append(pid)
                    ln.append(obj.node_name)
                elif et == ADDED:
                    lk.append(ADD)
                    lp.append(pid)
                    ln.append(None)
        self.spent["watch_drain"] += time.perf_counter() - t0
        return binds

    def close(self) -> None:
        self.watch.stop()
