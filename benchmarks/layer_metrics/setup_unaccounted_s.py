"""setup_s less the ledger's top-level phases and less the trace, lower and
backend seconds of the programs built outside any phase: the device
running the checks and the warm-up, imports, eager dispatch, the harness's
own Python. What the ledger cannot see, as a number (startup_reduce.py
has the account). Layer: Device. Moves setup_s."""

import startup_reduce


def compute(run: dict, trace: dict | None):
    return startup_reduce.metric(run, "setup_unaccounted_s")
