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

The same call installs the start-up ledger's listeners (`install_ledger`,
as the first `Net` built does where no entry point enabled a cache):
`programs` is the one place in the program that listens to jax's build
events. It keeps one row for each program jax traced, lowered or built,
keyed by the `fun_name` jax 0.9.0 hands a listener (`step` for the trace,
`jit(step)` for the other two: one row, `step`), and the events
themselves with their `time.perf_counter` stamps, folded so that no second
is counted twice: a `jnp` function traced inside the step's trace, a key's
`threefry` traced inside its lowering and a constant built eagerly inside
either all report events of their own. Bounded at `EVENT_CAP` events;
what is past it still reaches the rows' sums and is counted in `dropped`.
utils/spans.py prints it (`ledger.table`).

The reference has no analogue: it compiles ahead of time with nvcc and
has no JIT compilation step to cache.
"""

from __future__ import annotations

import os
import threading
import time

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


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
KINDS = ("trace", "lower", "backend")
EVENT_CAP = 16384
_KIND_OF = {TRACE_EVENT: 0, LOWER_EVENT: 1, BACKEND_EVENT: 2}
_BACKEND = _KIND_OF[BACKEND_EVENT]
# jax times an event on `time.time` and the listener stamps its end on
# `perf_counter` some microseconds later: an event that starts this much
# before another's computed start can still lie inside it
_SLACK_S = 50e-6
_clock = time.perf_counter


class ProgramRow:
    """What jax spent on the programs of one name: seconds as jax reports
    them (a trace's include what was traced inside it), `retrieval_s` the
    part of `backend_s` that read the persistent cache, `built` programs
    (backend events), of them `hits` loaded from the cache and `misses`
    compiled and written to it, `built_at` their `perf_counter` stamps."""
    __slots__ = ("trace_s", "lower_s", "backend_s", "retrieval_s", "hits",
                 "misses", "built", "built_at")

    def __init__(self):
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.retrieval_s = 0.0
        self.hits = self.misses = self.built = 0
        self.built_at: list[float] = []

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class BuildEvent:
    """One event that lies inside no other on its thread. `seconds` splits
    its duration by kind: its own kind's share is what no event inside it
    covered, the others' what the events inside it reported; `built`,
    `hits` and `misses` count the programs built inside it too."""
    __slots__ = ("name", "kind", "start", "end", "thread", "seconds",
                 "built", "hits", "misses")

    def __init__(self, name, kind, start, end, thread):
        self.name, self.kind, self.start, self.end = name, kind, start, end
        self.thread = thread
        self.seconds = [0.0, 0.0, 0.0]
        self.built = self.hits = self.misses = 0

    def as_list(self) -> list:
        return [self.name, KINDS[self.kind], self.start, self.end,
                *self.seconds, self.built, self.hits, self.misses]


class ProgramLedger:
    """The rows and the events (this module's docstring). The listeners
    below write into the module's `programs`, whichever instance that is
    when an event arrives: a test puts a fresh one there."""

    def __init__(self):
        self.installed_at: float | None = None
        self.rows: dict[str, ProgramRow] = {}
        self.events: list[BuildEvent] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._pending = threading.local()   # cache events before a build

    def cache_event(self, field: str, amount) -> None:
        """A cache hit, miss or retrieval time: jax names no program with
        it; it belongs to the build event that follows on this thread."""
        pending = vars(self._pending)
        pending[field] = pending.get(field, 0) + amount

    def add(self, kind: int, name: str, seconds: float) -> None:
        end = _clock()
        start = end - seconds
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        event = BuildEvent(name, kind, start, end, threading.get_ident())
        event.seconds[kind] = seconds
        pending = vars(self._pending)
        with self._lock:
            row = self.rows.get(name)
            if row is None:
                row = self.rows[name] = ProgramRow()
            field = KINDS[kind] + "_s"
            setattr(row, field, getattr(row, field) + seconds)
            if kind == _BACKEND:
                event.built = 1
                event.hits = pending.pop("hits", 0)
                event.misses = pending.pop("misses", 0)
                row.built += 1
                row.hits += event.hits
                row.misses += event.misses
                row.retrieval_s += pending.pop("retrieval_s", 0.0)
            # what this event encloses arrived before it, on this thread
            events = self.events
            i = len(events)
            while i and events[i - 1].end > start:
                i -= 1
                inner = events[i]
                if inner.thread != event.thread \
                        or inner.start < start - _SLACK_S:
                    continue
                del events[i]
                for k, s in enumerate(inner.seconds):
                    event.seconds[k] += s
                    event.seconds[kind] -= s
                event.built += inner.built
                event.hits += inner.hits
                event.misses += inner.misses
            if len(events) < EVENT_CAP:
                events.append(event)
                if kind == _BACKEND:
                    row.built_at.append(end)
            else:
                self.dropped += 1

    def sums(self, start: float = float("-inf"),
             end: float = float("inf")) -> dict:
        """Seconds by kind and programs built over the events that ended
        in [start, end]."""
        out = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
               "built": 0, "hits": 0, "misses": 0}
        for e in list(self.events):
            if start <= e.end <= end:
                for k, kind in enumerate(KINDS):
                    out[kind + "_s"] += e.seconds[k]
                out["built"] += e.built
                out["hits"] += e.hits
                out["misses"] += e.misses
        return out

    def built_between(self, start: float, end: float) -> int:
        return self.sums(start, end)["built"]

    def snapshot(self) -> dict:
        """Plain JSON values, for utils/spans.py `Ledger.snapshot`."""
        with self._lock:
            return {"installed_at": self.installed_at,
                    "dropped": self.dropped,
                    "rows": {k: r.as_dict() for k, r in self.rows.items()},
                    "events": [e.as_list() for e in self.events]}


programs = ProgramLedger()
_listening = False
_install_lock = threading.Lock()


def _on_duration(event: str, duration: float, fun_name: str = "", **_):
    kind = _KIND_OF.get(event)
    if kind is not None:
        programs.add(kind, fun_name, duration)
    elif event == RETRIEVAL_EVENT:
        programs.cache_event("retrieval_s", duration)


def _on_event(event: str, **_):
    if event == HIT_EVENT:
        programs.cache_event("hits", 1)
    elif event == MISS_EVENT:
        programs.cache_event("misses", 1)


def install_ledger() -> None:
    """Register the ledger's listener pair with `jax.monitoring`, once a
    process (jax has no call to remove one), and stamp `programs` with the
    moment it began to listen."""
    global _listening
    import jax
    with _install_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
        if programs.installed_at is None:
            programs.installed_at = _clock()


def enable_compile_cache() -> str:
    """Returns the cache dir in use ('' = the launcher disabled it)."""
    import jax
    install_ledger()
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
