"""The one place a Pallas kernel's execution mode is decided.

A kernel in this tree runs one of two ways: compiled by Mosaic for the
TPU, or interpreted (the kernel body evaluated as ordinary XLA ops) so
the CPU test suite executes the same kernel logic. The choice follows
the platform the surrounding program is LOWERED for
(`lax.platform_dependent`), not the process's default backend: a
deviceless `jit(...).trace(...).lower(lowering_platforms=("tpu",))` on
a CPU-only host therefore builds the Mosaic kernel
(tests/test_tpu_aot_compile.py), and no platform other than `cpu` can
ever receive the interpreter — it compiles the kernel or fails.

The reference has no analogue: its hand-written kernels are CUDA
sources next to each layer (e.g. src/caffe/layers/lrn_layer.cu), built
by nvcc with no interpreter to choose.
"""

from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl

from ..utils import spans


def pallas_call(kernel, *, interpret: bool | None = None, **kwargs):
    """`pl.pallas_call` with `interpret=None` meaning "by lowering
    platform": the interpreter on `cpu`, Mosaic everywhere else. An
    explicit bool is passed through (tests pin `True`; a chip-side
    parity check pins `False`)."""
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    name = kwargs.get("name") or getattr(
        getattr(kernel, "func", kernel), "__name__", "kernel")

    def arm(branch: str, interpret: bool):
        built = pl.pallas_call(kernel, interpret=interpret, **kwargs)

        def traced(*args):
            # jax traces BOTH arms of `platform_dependent` wherever the
            # lowering platform is not known yet: each trace is a phase of
            # the start-up ledger, named by kernel and arm (utils/spans.py)
            with spans.phase(spans.KERNEL, kernel=name, branch=branch):
                return built(*args)
        return traced

    # built once a `pallas_call`, not once a call: a memoized builder
    # (ops/flash_attention.py) hands out one `call`, and jax's own caches
    # key on the arms' identity
    interpreted, compiled = arm("cpu", True), arm("default", False)

    def call(*args):
        return lax.platform_dependent(*args, cpu=interpreted,
                                      default=compiled)
    return call
