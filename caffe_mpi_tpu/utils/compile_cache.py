"""Persistent XLA compilation cache: one rule for every entry point.

The AlexNet-class training step costs tens of seconds to compile on
TPU; a warm disk cache turns a repeat invocation into a cache hit. The
directory is part of the cache key, so it must never move between runs:

- `JAX_COMPILATION_CACHE_DIR` set (to anything, the empty string
  included): jax reads the variable itself and this program sets no
  cache directory in code — whoever launched the process owns the
  placement.
- unset: `<checkout>/.jax_cache`, computed from where this package
  lives (gitignored) — never from `~`, a temp name, a pid or the time.

`caffe` (cli.py), the serving engine, chip_smoke.py, benchmarks/run.py
and the tools all call `enable_compile_cache()` with no argument, so a
benchmark run and `caffe train` share compiles. The same call puts the
HLO metadata into the cache key: the scope names a profiler trace is read
by (utils/spans.py) live there, and an entry compiled before a name
changed must not be served after it.

The reference has no analogue: it compiles ahead of time with nvcc and
has no JIT compilation step to cache.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def runtime_tag() -> str:
    """Version tag binding a serialized XLA executable to the runtime
    that produced it — jax + jaxlib versions plus the backend platform
    and device kind. The program bank (serving/program_bank.py) folds
    this into every entry fingerprint, so a jaxlib upgrade or a
    different accelerator silently misses the bank and recompiles
    instead of deserializing an incompatible program. Touches the
    backend (jax.devices()), so only call when device work is imminent
    — the netshape admission planner stays jax-free."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    jaxlib_ver = getattr(jaxlib, "__version__", "?")
    return (f"jax-{jax.__version__}/jaxlib-{jaxlib_ver}"
            f"/{dev.platform}/{dev.device_kind}")


def enable_compile_cache() -> str:
    """Returns the cache dir in use ('' = the launcher disabled it)."""
    import jax
    # a profiler trace is read by the scope names in the executable's
    # metadata (utils/spans.py). jax leaves metadata out of the cache key
    # by default, so an executable cached by an older source would be
    # served with the older names (seen on jax 0.9.0: a step compiled
    # under scope A, then requested under scope B, traces as A)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed is not None:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
