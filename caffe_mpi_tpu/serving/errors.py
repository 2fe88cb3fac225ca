"""Typed serving-plane failures (ISSUE 12).

Reference: the reference deployment surface (examples/web_demo/app.py,
python/caffe/classifier.py) has exactly one failure mode — an unhandled
exception that takes the Flask worker down and surfaces as a generic
500. A production serving plane needs *typed*, *bounded* failures:
a shed request under overload is not a crashed model, a request that
aged past its deadline is not a corrupt upload, and a closed engine is
neither. Every class here carries the machine-readable `kind` the HTTP
front puts in its JSON body and the `http_status` it maps to, so
clients can implement backpressure (429 => retry with backoff,
504 => the answer is stale anyway, 503 => find another replica)
instead of parsing error prose.
"""

from __future__ import annotations


class ServingError(RuntimeError):
    """Base class for typed serving-plane failures."""

    kind = "error"
    http_status = 500


class ShedError(ServingError):
    """Load-shedding admission control (serve_queue_limit): the
    per-engine backlog is at its bound and this request was refused at
    submit time — fail fast instead of growing an unbounded queue whose
    every entry will miss its deadline anyway."""

    kind = "shed"
    http_status = 429


class EngineUnhealthyError(ShedError):
    """The dispatch stall breaker is open (a device call blew past
    `serve_stall_s`): requests shed immediately
    instead of queueing behind a hung dispatch. A recovery probe
    closing the breaker clears this."""

    kind = "unhealthy"
    http_status = 503


class DeadlineError(ServingError):
    """The request could not dispatch before its `serve_deadline_ms`
    deadline (checked at window close), or its in-flight dispatch was
    declared stalled by the breaker — either way the caller gets a
    bounded timeout instead of an unbounded wait."""

    kind = "deadline"
    http_status = 504


class EngineClosedError(ServingError):
    """The engine is shut down (or draining for shutdown): no new
    requests are accepted."""

    kind = "closed"
    http_status = 503


class SwapError(ServingError):
    """A verified hot-swap candidate was rejected — corrupt snapshot
    bytes, unloadable/shape-mismatched weights, or a failed canary
    forward (non-finite or wrong-shaped scores). The previous weights
    keep serving; the rejection is journaled."""

    kind = "swap"
    http_status = 500
