"""Contained child processes for device work.

A TPU chip belongs to one process at a time: a parent that has touched
jax holds it, and so does an orphaned child — every later process that
needs the chip then fails or hangs at backend start-up. A hung device
call sits inside C++ where no Python signal handler runs. So a tool
that launches device work runs it in a child process with a hard
deadline, stays off jax itself, and kills the child's whole process
group AND reaps it on every exit path (an unreaped zombie pollutes the
`ps` sweep an operator uses to find who holds the chip).

Used by chip_smoke.py and the training supervisors (utils/resilience.py).
"""

from __future__ import annotations

import atexit
import os
import signal
import subprocess

# pgids of live contained children: killed from atexit AND from
# SIGTERM/SIGINT — a `timeout`/`kill` on the PARENT otherwise leaves the
# child alive in its own session, holding the chip
_ACTIVE: set[int] = set()
_HOOKED = False


def _reap_all(signum=None, frame=None):
    for pgid in list(_ACTIVE):
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    if signum is not None:  # re-deliver default behavior
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def _install_hooks():
    global _HOOKED
    if _HOOKED:
        return
    _HOOKED = True
    atexit.register(_reap_all)
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        try:
            signal.signal(sig, _reap_all)
        except (ValueError, OSError):  # non-main thread / unsupported
            pass


def run_contained(cmd: list[str], timeout: float | None,
                  cwd: str | None = None, env: dict | None = None,
                  echo: bool = False, tail_lines: int = 400):
    """Run cmd in its own process group with a hard deadline.

    Returns (returncode|None, stdout, stderr) — returncode None means
    the deadline expired. The group is SIGKILLed and the child reaped on
    every exit path, including the parent being SIGTERM'd.

    timeout=None disables the deadline (supervised training children:
    the in-child dispatch watchdog owns hang detection there, and a
    multi-hour run must not be killed by an arbitrary cap). echo=True
    streams the child's output to this process's stdout/stderr as it
    arrives (training logs stay live under supervision) while still
    returning the last `tail_lines` lines of each — memory stays bounded
    on runs that log for hours.
    """
    _install_hooks()
    # Mask the handled signals across Popen -> _ACTIVE.add: a SIGTERM
    # landing in that window would run _reap_all without knowing the new
    # child, leaking a chip-claiming orphan — the exact failure this
    # module exists to prevent. Caveat: pthread_sigmask masks THIS thread
    # only, so the window closes fully only for single-threaded callers
    # (chip_smoke — the one that matters); a process-directed signal may
    # still land on another unblocked thread.
    _sigs = {signal.SIGTERM, signal.SIGINT, signal.SIGHUP}
    try:
        prev_mask = signal.pthread_sigmask(signal.SIG_BLOCK, _sigs)
    except (ValueError, OSError):  # non-main thread restrictions etc.
        prev_mask = None
    try:
        proc = subprocess.Popen(cmd, cwd=cwd, env=env, text=True,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE,
                                start_new_session=True)
        _ACTIVE.add(proc.pid)
    finally:
        if prev_mask is not None:
            signal.pthread_sigmask(signal.SIG_SETMASK, prev_mask)
    try:
        if echo:
            rc, out, err = _pump_echo(proc, timeout, tail_lines)
            return rc, out, err
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        # child is now SIGKILLed: drain pipes and reap the zombie
        out, err = proc.communicate()
        return None, out, err
    finally:
        _kill_group(proc)
        proc.wait()
        _ACTIVE.discard(proc.pid)


def _pump_echo(proc: subprocess.Popen, timeout: float | None,
               tail_lines: int):
    """Mirror the child's pipes to this process's streams line by line,
    keeping only a bounded tail of each. Returns (rc|None, out_tail,
    err_tail) — rc None means the deadline expired (group killed, same
    contract as the communicate() path)."""
    import sys
    import threading
    from collections import deque

    tails = {"out": deque(maxlen=tail_lines), "err": deque(maxlen=tail_lines)}

    def pump(pipe, sink, key):
        for line in pipe:
            tails[key].append(line)
            sink.write(line)
            sink.flush()

    threads = [
        threading.Thread(target=pump, args=(proc.stdout, sys.stdout, "out"),
                         daemon=True),
        threading.Thread(target=pump, args=(proc.stderr, sys.stderr, "err"),
                         daemon=True),
    ]
    for t in threads:
        t.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        _kill_group(proc)
        proc.wait()
    for t in threads:  # pipes hit EOF once the group is dead
        t.join(timeout=5)
    return (None if timed_out else proc.returncode,
            "".join(tails["out"]), "".join(tails["err"]))


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
