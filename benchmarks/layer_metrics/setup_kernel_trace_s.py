"""Seconds inside the ledger's `trace/kernel` phases in set-up: one phase a
trace of a Pallas kernel's caller, both arms of `lax.platform_dependent`
(count by kernel and arm on the earlier line). Layer: Pallas_kernels.
Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_kernel_trace_s")
