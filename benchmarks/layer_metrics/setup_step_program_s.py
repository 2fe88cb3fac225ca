"""Trace + lower + backend seconds of the train step's program alone
(`Solver`'s `step` or `multi_step`): the inside of setup_compile_s, which
also holds the first step's device time and the display boundary's read.
Layer: Compile_cache. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_step_program_s")
