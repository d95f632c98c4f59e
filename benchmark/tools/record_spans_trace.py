#!/usr/bin/env python3
"""Record the small trace that benchmark/tests/test_span_readers.py keeps:
two windows of a tiny cluster's scheduler on the TPU, driven as the closed
loop drives it, inside the benchmark's span names. The program's own spans
(`obs.trace.span`) and its kernels' named scopes are in it.

    python3 benchmark/tools/record_spans_trace.py <out.xplane.pb.gz>

Gzipped: the profiler keeps the HLO of every program it saw, and the K-batch
kernel's is 3.5 MB of the 3.9.
"""
import gzip
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> int:
    import kubernetes_tpu.ops  # noqa: F401  (cache dir + x64, before jax use)
    import jax
    from lib import trace as tr
    if jax.devices()[0].platform != "tpu":
        print("record_spans_trace: needs a TPU", file=sys.stderr)
        return 2
    from kubernetes_tpu.api.types import Container, Node, Pod
    from kubernetes_tpu.scheduler import Scheduler
    from kubernetes_tpu.store.store import NODES, PODS, Store
    store = Store()
    for i in range(48):
        store.create(NODES, Node(name=f"n{i}", allocatable={
            "cpu": 8000, "memory": 32 << 30, "pods": 110}))
    sched = Scheduler(store, use_tpu=True, percentage_of_nodes_to_score=100)
    sched.sync()

    def cycle(tag: str, n: int) -> None:
        pods = [Pod(name=f"{tag}-{j}", labels={"app": "x"}, containers=(
            Container.make(name="c", requests={"cpu": 100}),))
            for j in range(n)]
        with jax.profiler.TraceAnnotation("client.create"):
            store.create_many(PODS, pods)
        with jax.profiler.TraceAnnotation("sched.pump"):
            sched.pump()
        while True:
            with jax.profiler.TraceAnnotation("sched.schedule_burst"):
                if sched.schedule_burst(max_pods=64) == 0:
                    break
        with jax.profiler.TraceAnnotation("sched.pump"):
            sched.pump()
        with jax.profiler.TraceAnnotation("client.reap"):
            store.delete_many(PODS, [p.key for p in pods])
        with jax.profiler.TraceAnnotation("sched.pump"):
            sched.pump()

    for k in range(3):           # upload, both programs and the scatter
        cycle(f"warm{k}", 24)
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    for k in range(2):
        cycle(f"w{k}", 24)
    jax.profiler.stop_trace()
    with open(tr.newest_xplane(d), "rb") as src, gzip.open(out, "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(d, ignore_errors=True)
    print(out, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
