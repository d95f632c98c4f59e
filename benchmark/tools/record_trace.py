#!/usr/bin/env python3
"""Record the small trace that benchmark/tests/ keeps: a few launches of a
tiny jitted program on the TPU, inside the benchmark's span names.

    python3 benchmark/tools/record_trace.py <out.xplane.pb>
"""
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from lib import trace as tr
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("client.create"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("sched.schedule_burst"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(tr.newest_xplane(d), out)
    shutil.rmtree(d, ignore_errors=True)
    print(out, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
