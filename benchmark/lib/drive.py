"""The two drives: a closed loop over backlogs, an open loop over arrivals.

Both are one cooperative thread, as `perf/harness.run_serve_cell` is (copied
in structure): the client's calls and the scheduler's steps alternate, so a
seed gives one sequence of events and no thread scheduling noise. All clocks
are the client's.
"""
from __future__ import annotations

import bisect
import random
import time
from collections import deque

from lib.client import BIND


class Tracer:
    """Profiler trace over `cap_s` seconds of a window, starting `start_after_s`
    into it. Stopped at a loop boundary, where no pod is waiting on it."""

    def __init__(self, log_dir: str | None, cap_s: float,
                 start_after_s: float = 0.0):
        self.log_dir = log_dir
        self.cap_s = cap_s
        self.start_after_s = start_after_s
        self.t0 = self.t1 = None
        self.stop_s = None           # what stopping (serialising) cost
        self.active = False

    def maybe_start(self, window_age_s: float) -> None:
        if self.log_dir is None or self.t0 is not None \
                or window_age_s < self.start_after_s:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.active = True
        self.t0 = time.perf_counter()

    def maybe_stop(self, force: bool = False) -> None:
        if not self.active:
            return
        now = time.perf_counter()
        if force or now - self.t0 >= self.cap_s:
            import jax
            self.t1 = now
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - now
            self.active = False


def drain_scheduler(client, sched, max_pods: int) -> int:
    """As `cmd/scheduler.py` and `chip_smoke.drain` drive it: pump, burst
    until nothing is bound, pump."""
    with client.span("sched.pump"):
        sched.pump()
    bound = 0
    while True:
        with client.span("sched.schedule_burst"):
            n = sched.schedule_burst(max_pods=max_pods)
        if n == 0:
            break
        bound += n
    with client.span("sched.pump"):
        sched.pump()
    return bound


def backlog_cycle(client, sched, factory, backlog: int, tag: str) -> dict:
    """One closed-loop cycle: make the pods, submit them, drive the
    scheduler until all are seen bound, delete them (the hollow 'workload
    finished') and let the scheduler digest the deletes. `seconds` is the
    part with pods pending, from just before `create_many` to the return of
    the watch drain that showed the last bind: a per-layer reading. The
    judged rate is over the whole window (`run_backlog`)."""
    t_make = time.perf_counter()
    factory.new_cycle()
    made = [factory.make(f"{tag}-{j}") for j in range(backlog)]
    pods = [p for p, _d in made]
    ids = [client.register(p, d) for p, d in made]
    t0 = time.perf_counter()
    client.spent["make"] += t0 - t_make
    cpu0 = time.process_time()
    accepted, _retry = client.create(pods)
    drain_scheduler(client, sched, backlog)
    client.drain()
    t1 = time.perf_counter()
    cpu_s = time.process_time() - cpu0
    seen = sum(1 for i in ids if client.bind_seen_at[i] > 0.0)
    client.delete([client.keys[i] for i in ids])
    with client.span("sched.pump"):
        sched.pump()
    client.drain()
    return {"seconds": t1 - t0, "attempted": len(pods), "accepted": accepted,
            "bound_seen": seen, "cpu_s": cpu_s}


def run_backlog(client, sched, factory, traffic: dict, seconds: float,
                tracer: Tracer) -> dict:
    """Whole cycles until `seconds` are up; the cycle in flight then is
    finished and counts. The window is everything from `t_start` to `t_end`,
    the last cycle's deletes digested."""
    cycles = []
    tracer.maybe_start(0.0)
    t_start = time.perf_counter()
    while True:
        cycles.append(backlog_cycle(client, sched, factory, traffic["backlog"],
                                    f"bl-{len(cycles)}"))
        tracer.maybe_stop()
        if time.perf_counter() - t_start >= seconds:
            break
    tracer.maybe_stop(force=True)
    # a traced run's window does not count the seconds the profiler took to
    # write its trace out between two cycles
    t_end = time.perf_counter() - (tracer.stop_s or 0.0)
    return {"t_start": t_start, "t_end": t_end, "cycles": cycles,
            "attempted": sum(c["attempted"] for c in cycles),
            "bound_seen": sum(c["bound_seen"] for c in cycles)}


def run_arrivals(client, loop, made: list, due: list, traffic: dict,
                 seconds: float, seed: int, tracer: Tracer) -> dict:
    """Open loop (a traced run traces the window's last seconds and the
    settle). Arrival i is submitted at the first tick at or after
    `t_start + due[i]`, in one `create_many` per tick; a 429 re-queues the
    shed tail after the server's suggested back-off (jittered, capped at 5 s,
    `give_up_after` attempts). Each pod is deleted `lifetime_s` after the
    client saw it bound. After the window the loop settles until every
    arrival has an outcome."""
    serve = traffic["serve"]
    lifetime = float(traffic["lifetime_s"])
    rng = random.Random(seed ^ 0xBACC0FF)
    pods = [p for p, _d in made]
    ids = [client.register(p, d) for p, d in made]
    first = ids[0] if ids else 0     # ids below it are warm-up pods
    n = len(pods)
    submit_at = [0.0] * n
    attempts = [0] * n
    gave_up: set = set()
    retry: list = []                 # (when, index)
    expire: deque = deque()          # (when, key), in bind-seen order
    rejected = 0
    nxt = 0
    log_pos = len(client.log_kind)
    t_start = time.perf_counter()
    t_end = t_start + seconds
    settle_deadline = t_end + float(serve["settle_timeout_s"])
    # arrivals due and neither seen bound nor given up: the client's own view
    # of the backlog (a late generator's unsubmitted arrivals included), read
    # at the middle and the end of the window
    n_seen = 0
    # the slowest call of each kind, and when in the window it began
    slowest = {k: (0.0, 0.0) for k in ("reap", "create", "step", "drain")}

    def took(kind: str, began: float) -> None:
        dt = time.perf_counter() - began
        if dt > slowest[kind][0]:
            slowest[kind] = (dt, began - t_start)

    def backlog(now: float) -> int:
        return bisect.bisect_right(due, now - t_start) - n_seen - len(gave_up)

    mid_depth = end_depth = None
    while True:
        now = time.perf_counter()
        if now >= t_end:
            if end_depth is None:
                end_depth = backlog(now)
            if (nxt >= n and not retry and n_seen + len(gave_up) >= n) \
                    or now >= settle_deadline:
                break
        else:
            tracer.maybe_start(now - t_start)
            if mid_depth is None and now >= t_start + seconds / 2:
                mid_depth = backlog(now)
        # completions: pods whose lifetime is over are deleted
        if expire and expire[0][0] <= now:
            keys = []
            while expire and expire[0][0] <= now:
                keys.append(expire.popleft()[1])
            t_call = time.perf_counter()
            client.delete(keys)
            took("reap", t_call)
        # retries whose back-off is over come first: they arrived earlier
        batch_idx = []
        if retry:
            due_now = [r for r in retry if r[0] <= now]
            if due_now:
                retry = [r for r in retry if r[0] > now]
                due_now.sort()
                batch_idx.extend(i for _t, i in due_now)
        j = nxt
        while j < n and t_start + due[j] <= now:
            submit_at[j] = now
            j += 1
        batch_idx.extend(range(nxt, j))
        nxt = j
        if batch_idx:
            t_call = time.perf_counter()
            accepted, retry_after = client.create([pods[i] for i in batch_idx])
            took("create", t_call)
            for i in batch_idx[accepted:]:
                rejected += 1
                attempts[i] += 1
                if attempts[i] >= serve["give_up_after"]:
                    gave_up.add(i)
                    continue
                delay = min(retry_after, 5.0) * (0.5 + rng.random())
                retry.append((now + delay, i))
        t_call = time.perf_counter()
        with client.span("loop.step"):
            bound = loop.step()
        took("step", t_call)
        t_call = time.perf_counter()
        seen_now = client.drain()
        took("drain", t_call)
        if seen_now:
            lk, lp = client.log_kind, client.log_pod
            for k in range(log_pos, len(lk)):
                if lk[k] == BIND:
                    pid = lp[k]
                    if pid >= first:
                        n_seen += 1
                    expire.append((client.bind_seen_at[pid] + lifetime,
                                   client.keys[pid]))
        log_pos = len(client.log_kind)
        if bound == 0:
            time.sleep(min(loop.tick_interval, 0.001))
    t_done = time.perf_counter()
    tracer.maybe_stop(force=True)     # after the settle: no pod waits on it
    latencies, late = [], []
    unbound = 0
    for k, pid in enumerate(ids):
        seen = client.bind_seen_at[pid]
        if k in gave_up or seen == 0.0:
            unbound += 1
            continue
        latencies.append(seen - (t_start + due[k]))
        late.append(submit_at[k] - (t_start + due[k]))
    # the course of the window in ten slices by due time: (median, worst) ms
    slices = [[] for _ in range(10)]
    for k, pid in enumerate(ids):
        seen = client.bind_seen_at[pid]
        if seen > 0.0 and k not in gave_up:
            slices[min(9, int(10 * due[k] / seconds))].append(
                seen - (t_start + due[k]))
    course = [(round(1e3 * sorted(x)[len(x) // 2], 1), round(1e3 * max(x), 1))
              if x else None for x in slices]
    return {"t_start": t_start, "t_end": t_end, "t_done": t_done,
            "course": course,
            "slowest": {k: (round(v[0], 4), round(v[1], 2))
                        for k, v in slowest.items()},
            "attempted": n, "failed": unbound, "gave_up": len(gave_up),
            "rejected_429": rejected, "latencies": latencies, "late": late,
            "bound_seen": n - unbound,
            "mid_depth": mid_depth, "end_depth": end_depth}
