"""JAX device layer: dense node-state encoding and filter/score/select kernels.

Importing this package configures jax for the framework: 64-bit integers are
enabled because the reference's resource math is int64 (milliCPU ints,
memory in bytes, scores summed as int64 — pkg/scheduler/api/types.go:35) and
exact score parity requires the same arithmetic on device.

It also places the persistent compilation cache, before anything compiles.
The kernels specialise on padded shapes and a dozen static arguments, so a
process that starts cold recompiles every program it touches. When
`JAX_COMPILATION_CACHE_DIR` is set, jax reads it itself and nothing is set
here; otherwise the cache lives at `<checkout>/.jax_cache`, derived from
this file's own location (the path is part of the cache key, so it must not
depend on a temp name, a pid or the time).

The cache key takes in the operations' metadata. The kernels name their
stages with `jax.named_scope` (filter, score, pick, fold: the names a device
trace is read by), and a scope is metadata only, which jax leaves out of the
key by default: an executable cached before a scope was added or renamed
would come back without it, and the trace would silently lose its names.
With the metadata in the key such an executable is a miss and is compiled
once more, here and after any edit that moves a traced line.

Compiles are counted here too, where the cache is placed: jax's monitoring
event for an executable built or fetched from the cache names the jitted
function, so `tpu_compiles_total{program}` says which program (re)compiled.
"""
import os

import jax

from kubernetes_tpu import obs

jax.config.update("jax_enable_x64", True)

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

if not os.environ.get(COMPILE_CACHE_ENV):
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILES = obs.counter(
    "tpu_compiles_total",
    "Executables built or fetched from the persistent cache, by jitted "
    "function. Moves during warm-up only: a steady-state increase is a "
    "shape or static argument the warm-up did not cover. The first "
    "32 programs of a process have a child each, later ones share "
    "program=\"other\".", ("program",))
COMPILE_SECONDS = obs.counter(
    "tpu_compile_seconds_total",
    "Seconds spent building executables or fetching them from the "
    "persistent cache, by jitted function.", ("program",))


# a scheduler process compiles a dozen or two programs; a process that
# compiles hundreds (a test worker: every eager jnp call is a program) must
# not grow the registry without bound, so later names share one child
MAX_COMPILE_PROGRAMS = 32
_compile_programs: set = set()


def _on_compile(event: str, seconds: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    program = str(kw.get("fun_name") or "unknown")
    if program not in _compile_programs:
        if len(_compile_programs) >= MAX_COMPILE_PROGRAMS:
            program = "other"
        else:
            _compile_programs.add(program)
    COMPILES.labels(program).inc()
    COMPILE_SECONDS.labels(program).inc(seconds)


jax.monitoring.register_event_duration_secs_listener(_on_compile)
