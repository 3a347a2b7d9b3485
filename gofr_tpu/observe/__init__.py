"""Inference flight recorder + ``/debug`` introspection subsystem.

The always-on observability layer for the serving stack (PAPER.md layer
map row "Observability", extended TPU-side): an in-flight request
registry, a bounded ring buffer of request lifecycle events, a
wall-clock sampling profiler, and the ``/debug`` pages that render them.
One ``Observe`` object lives on the Container and is threaded through
HTTP middleware and the TPU engines.
"""

from __future__ import annotations

from .clock import ClockRegistry, PeerClock
from .profiler import collect_profile, render_collapsed, sample_once
from .recorder import FlightRecorder
from .registry import InflightRequest, RequestRegistry
from .startup import StartupAccount
from .timeline import Timeline, _enabled_from_env, timeline_from_config

__all__ = [
    "Observe",
    "ClockRegistry",
    "FlightRecorder",
    "InflightRequest",
    "PeerClock",
    "RequestRegistry",
    "StartupAccount",
    "Timeline",
    "collect_profile",
    "render_collapsed",
    "sample_once",
    "timeline_from_config",
]


class Observe:
    """The container's observability bundle: request registry + flight
    recorder + serving timeline + start-up account + the tracer the
    serving stack emits stage spans through. Always constructed (the recorder and timeline
    are bounded rings and the registry is O(active requests)) —
    observability is not opt-in."""

    def __init__(self, metrics=None, tracer=None, max_events: int = 2048,
                 timeline: "Timeline | None" = None,
                 clock: "ClockRegistry | None" = None):
        self.requests = RequestRegistry()
        self.recorder = FlightRecorder(capacity=max_events)
        self.metrics = metrics
        self.tracer = tracer
        # fleet clock registry (clock.py): peer offset estimates fed by
        # the pd handshake and the gateway health poll, read by the
        # fleet timeline merge and /debug/request
        self.clock = clock if clock is not None else ClockRegistry()
        # serving timeline (timeline.py): defaults honor the
        # TPU_TIMELINE / TPU_TIMELINE_EVENTS process environment so
        # engine-level constructions (tests, benches) behave like the
        # container wiring, which passes timeline_from_config(config)
        self.timeline = timeline if timeline is not None else Timeline(
            enabled=_enabled_from_env())
        # the engine's account of its start-up (startup.py): one a
        # process, written by the config wiring, the engines' buffers and
        # programs and their warm-ups
        self.startup = StartupAccount(self.timeline, metrics, tracer)
