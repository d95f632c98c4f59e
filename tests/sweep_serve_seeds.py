"""Serve-window parity seed sweep (the round-16 42-trial run).

Not collected by pytest (no test_ prefix): run by hand after any serve
loop, launch, backpressure, or shell-burst change —

    JAX_PLATFORMS=cpu python tests/sweep_serve_seeds.py [trials] [base_seed]

Each trial re-runs the arrival-driven differential fuzz
(tests/test_serve.TestServeWindowParity) with a fresh seed: the same
arrival schedule fed through ServeLoop windows on the TPU burst path vs
a serial oracle shell observing the arrivals at the same window
boundaries, asserting bit-identical final bindings. The trial mix
rotates through the plain fuzz, the mid-window node-death variant (the
launch-refusal contract under arrival load), the blanket-injection
variant (graceful degradation), and the deterministic-shed variant (the
429 path inside the parity harness); window size, launch depth, round
count, and the pod-class mix all re-draw per seed.
"""
import random
import sys
from contextlib import contextmanager

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import tests.conftest  # noqa: F401  (forces the 8-device CPU mesh config)


@contextmanager
def _flight_recorder():
    from kubernetes_tpu.obs import flight
    flight.RECORDER.configure(mode="replay", capacity=64)
    flight.RECORDER.clear()
    try:
        yield flight.RECORDER
    finally:
        flight.RECORDER.configure(mode="digest")
        flight.RECORDER.clear()


def run_sweep(trials: int = 42, base_seed: int = 0) -> None:
    from kubernetes_tpu import chaos as chaos_mod
    from tests.test_serve import TestServeWindowParity
    rng = random.Random(base_seed)
    variants = [
        ("plain", {}),
        ("death", {"death": True}),
        ("chaos", {"chaos": True}),
        ("shed", {"shed_rate": 0.3}),
        # round-17: mid-window pod updates drive the encode-at-admission
        # row cache's update-in-place invalidation (cached-row vs
        # fresh-encode bit-identity asserted row-by-row inside the fuzz)
        ("update", {"update_rate": 0.4}),
    ]
    inst = TestServeWindowParity()
    for trial in range(trials):
        name, kw = variants[trial % len(variants)]
        seed = rng.randint(1, 10_000)
        try:
            with _flight_recorder() as rec:
                inst.test_serve_stream_identical(seed, rec, **kw)
        except Exception:
            print(f"FAIL variant={name} seed={seed}")
            raise
        finally:
            chaos_mod.disable()
        print(f"ok {trial + 1}/{trials} {name} seed={seed}")
    print(f"serve sweep green: {trials} trials")


if __name__ == "__main__":
    run_sweep(int(sys.argv[1]) if len(sys.argv) > 1 else 42,
              int(sys.argv[2]) if len(sys.argv) > 2 else 0)
