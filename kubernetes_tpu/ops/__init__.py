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
"""
import os

import jax

jax.config.update("jax_enable_x64", True)

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

if not os.environ.get(COMPILE_CACHE_ENV):
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
