"""Framework error hierarchy.

The reference maps handler errors to HTTP statuses in
pkg/gofr/http/responder.go:47-57 (nil -> 200, ErrorEntityNotFound -> 404,
else -> 500). Here the mapping is carried by the exception itself: any
handler may raise ``HTTPError`` (or subclass) with an explicit status;
unexpected exceptions become 500s in the recovery middleware
(reference pkg/gofr/http/middleware/logger.go:94-117).
"""

from __future__ import annotations

import math


def format_retry_after(seconds: float) -> str:
    """The one Retry-After wire formatter (HTTP header, gRPC trailer,
    drain responses): delta-seconds per RFC 9110 §10.2.3, ceiling so a
    90.4 s estimate never under-advises as 90, floored at 1 because 0
    invites an instant retry storm."""
    return str(max(1, math.ceil(seconds)))


def parse_retry_after(value: str | None) -> float | None:
    """The one Retry-After reader (retry decorator, gateway relay and
    replica table): delta-seconds to a non-negative float; ``None``
    for absent, garbage, or the HTTP-date form (rare — callers fall
    back to their own backoff/jitter)."""
    try:
        return max(0.0, float(value)) if value else None
    except (TypeError, ValueError):
        return None


class GofrError(Exception):
    """Base class for all framework errors."""


class HTTPError(GofrError):
    """An error with an explicit HTTP status code."""

    status_code: int = 500

    def __init__(self, message: str = "", status_code: int | None = None):
        super().__init__(message or self.__class__.__name__)
        if status_code is not None:
            self.status_code = status_code
        self.message = message or self.__class__.__name__

    def to_dict(self) -> dict:
        return {"message": self.message}


class BadRequest(HTTPError):
    status_code = 400


class Unauthorized(HTTPError):
    status_code = 401


class Forbidden(HTTPError):
    status_code = 403


class NotFound(HTTPError):
    status_code = 404


class EntityNotFound(NotFound):
    """Reference: pkg/gofr/http/errors.go ErrorEntityNotFound -> 404."""

    def __init__(self, name: str = "entity", value: str = ""):
        super().__init__(f"No {name} found for value {value!r}")
        self.name = name
        self.value = value


class ProgramNotFound(NotFound, KeyError):
    """An inference request named a TPU program the engine never
    registered -> 404 with the known-program list, instead of the raw
    500 a bare KeyError becomes. Subclasses KeyError so callers doing
    dict-style lookup-miss handling keep working."""

    def __init__(self, program: str, registered: list[str] | None = None):
        known = f"; registered: {sorted(registered)}" if registered else ""
        super().__init__(f"no TPU program {program!r}{known}")
        self.program = program

    # KeyError.__str__ repr()s the message (dict-miss convention);
    # wire errors must render the plain text
    __str__ = Exception.__str__


class InvalidParameter(BadRequest):
    def __init__(self, *params: str):
        super().__init__(f"Invalid parameter(s): {', '.join(params)}")
        self.params = params


class MissingParameter(BadRequest):
    def __init__(self, *params: str):
        super().__init__(f"Missing parameter(s): {', '.join(params)}")
        self.params = params


class ShardingConfigError(GofrError, ValueError):
    """A mesh/sharding configuration the engine refuses to serve with —
    raised at engine construction, before any request is accepted.
    Names the offending ``TPU_SHARDING`` row so the operator can fix
    the config line rather than chase wrong logits: the known case is a
    tp that splits a KV head (n_kv_heads % tp != 0) combined with
    dp/fsdp > 1, a VERIFIED wrong-logits hazard (see
    docs/advanced-guide/multichip-serving.md "known limits").
    Subclasses ValueError so config-validation callers that catch
    ValueError keep working."""

    def __init__(self, message: str, sharding_row: str = ""):
        super().__init__(message)
        self.sharding_row = sharding_row


class UnsupportedOptions(GofrError, ValueError):
    """Serving options the model's family does not run, refused at engine
    construction. ``refused`` holds (engine option, reason) pairs in the
    constructor's names; ``new_engine_from_config`` says them again in
    the configuration's (``TPU_*``)."""

    def __init__(self, refused: list[tuple[str, str]], who: str = ""):
        super().__init__(
            f"{who or 'this model family'} does not run: "
            + "; ".join(f"{opt}: {why}" for opt, why in refused))
        self.refused = refused


class InternalServerError(HTTPError):
    status_code = 500


class ServiceUnavailable(HTTPError):
    status_code = 503


class TooManyRequests(HTTPError):
    """Shed by an admission gate (resilience.AdmissionGate): the queue is
    over its configured bound, so the request fails FAST instead of
    joining a line that would blow its own latency budget. Carries the
    gate's wait estimate as ``Retry-After`` (the responder emits
    ``headers``; the gRPC transport maps 429 -> RESOURCE_EXHAUSTED).

    ``reason`` types the PRESSURE KIND on the wire as an
    ``X-Shed-Reason`` header (``hbm`` for arbiter memory sheds; absent
    means queue pressure) — a cross-process peer (the prefix-affinity
    gateway) balances a memory-shedding replica differently from a
    queue-deep one, and the header is the contract that distinction
    survives the hop on (parsing error-message prose would not)."""

    status_code = 429

    def __init__(self, message: str = "", retry_after: float | None = None,
                 reason: str | None = None):
        super().__init__(message or "too many requests")
        self.retry_after = retry_after
        self.reason = reason
        self.headers: dict[str, str] = {}
        if retry_after is not None:
            self.headers["Retry-After"] = format_retry_after(retry_after)
        if reason:
            self.headers["X-Shed-Reason"] = reason


class DeadlineExceeded(HTTPError):
    """The caller's deadline (gRPC ``grpc-timeout`` / HTTP
    ``X-Request-Timeout``) expired before the work completed — including
    while still queued, in which case the dispatcher dropped the item
    without ever executing it (resilience.md). 504 on HTTP; the gRPC
    transport maps it to DEADLINE_EXCEEDED."""

    status_code = 504

    def __init__(self, message: str = "deadline exceeded"):
        super().__init__(message)


class ConnectionLost(HTTPError, EOFError):
    """A transport peer vanished mid-exchange — socket closed, GOAWAY,
    half-read frame. 502 on HTTP (the upstream died, not us).
    Subclasses EOFError because EOFError is this repo's long-standing
    transport-loss sentinel: every ``except (EOFError, OSError)`` arm
    in wire/grpcx/pd keeps catching it unchanged."""

    status_code = 502

    def __init__(self, message: str = "connection lost"):
        super().__init__(message)


class CircuitOpenError(ServiceUnavailable):
    """Raised by the client-side circuit breaker while open
    (reference: pkg/gofr/service/circuit_breaker.go ErrCircuitOpen)."""

    def __init__(self, address: str = "") -> None:
        suffix = f" for {address}" if address else ""
        super().__init__(f"circuit breaker is open{suffix}")
        self.address = address


def status_from_error(err: BaseException | None) -> int:
    """Map an exception to an HTTP status (reference responder.go:47-57)."""
    if err is None:
        return 200
    if isinstance(err, HTTPError):
        return err.status_code
    return 500
