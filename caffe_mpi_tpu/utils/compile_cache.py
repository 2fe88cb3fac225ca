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

`caffe` (cli.py), the serving engine, bench.py and the tools all call
`enable_compile_cache()` with no argument, so a bench child and
`caffe train` share compiles.

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
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed is not None:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
