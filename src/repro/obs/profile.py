"""Profiling hooks: ``jax.profiler`` round capture and compile-event metrics.

Two optional instruments, both wired through the job spec's strict
``observability`` section:

- :class:`RoundProfiler` captures a ``jax.profiler`` trace around the
  first N rounds of a run (the designated rounds), writing TensorBoard-
  loadable artifacts under ``<run_dir>/jax_profile``.
- :class:`CompileWatcher` registers a ``jax.monitoring`` listener and
  counts backend compile requests (an XLA compile or a persistent-cache
  load) and their seconds, surfacing them as ``jit.compiles`` /
  ``jit.compile_time_s`` counters and a per-round ``jit.round_compiles``
  gauge — hot-path recompilation becomes an assertable regression rather
  than a silent slowdown — and, on a traced run, as ``compile`` spans
  naming the program.

Neither swallows a failure: a profiler that cannot start or stop, or a
listener that cannot register, raises into the run that asked for it, so a
run never exits 0 with its instrumentation silently missing.  jax is
imported only when an instrument is used, keeping ``repro.obs`` importable
without it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, resolve_tracer

# Defaults for the job spec's ``observability`` section.  ``None`` for the
# section itself means "observability off" (same tri-state contract as the
# ``privacy`` section).
OBSERVABILITY_DEFAULTS: dict[str, Any] = {
    "trace": True,
    "trace_capacity": 65536,
    "jax_profile_rounds": 0,
}


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """Validated ``observability`` job-spec section."""

    trace: bool = True
    trace_capacity: int = 65536
    jax_profile_rounds: int = 0


def resolve_observability(section: Mapping[str, Any] | None) -> ObservabilityConfig | None:
    """Strictly validate an ``observability`` section (``None`` = off)."""
    if section is None:
        return None
    if not isinstance(section, Mapping):
        raise ValueError(f"observability section must be an object or null, got {section!r}")
    merged = dict(OBSERVABILITY_DEFAULTS)
    for key, value in section.items():
        if key not in OBSERVABILITY_DEFAULTS:
            raise ValueError(
                f"unknown observability key {key!r}; valid keys: "
                f"{sorted(OBSERVABILITY_DEFAULTS)}"
            )
        merged[key] = value
    if not isinstance(merged["trace"], bool):
        raise ValueError(f"observability.trace must be a bool, got {merged['trace']!r}")
    for key in ("trace_capacity", "jax_profile_rounds"):
        value = merged[key]
        if not isinstance(value, int) or isinstance(value, bool) or value < 0:
            raise ValueError(f"observability.{key} must be a non-negative int, got {value!r}")
    if merged["trace_capacity"] < 1:
        raise ValueError("observability.trace_capacity must be >= 1")
    return ObservabilityConfig(**merged)


class RoundProfiler:
    """Capture a ``jax.profiler`` trace around the first ``rounds`` rounds.

    ``round_start``/``round_end`` are called by the round program with the
    global round index; capture begins at the first observed round and
    stops after ``rounds`` rounds have ended (so a resumed run profiles
    its own first rounds, where recompilation would show up).
    """

    def __init__(self, rounds: int, log_dir: str):
        self.rounds = int(rounds)
        self.log_dir = str(log_dir)
        self._active = False
        self._seen = 0

    def round_start(self, round_index: int) -> None:
        if self.rounds <= 0 or self._active or self._seen >= self.rounds:
            return
        import jax.profiler

        jax.profiler.start_trace(self.log_dir)
        self._active = True

    def round_end(self, round_index: int) -> None:
        if not self._active:
            return
        self._seen += 1
        if self._seen >= self.rounds:
            self.stop()

    def stop(self) -> None:
        if not self._active:
            return
        import jax.profiler

        self._active = False
        jax.profiler.stop_trace()


# The event jax records around every backend compile request: an XLA
# compile, or a load of the compiled program from the persistent cache
# (either stalls the caller).  Its duration listeners get ``fun_name``.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# One process-global jax.monitoring listener fans out to live watchers;
# jax exposes register but no public unregister, so the listener is
# installed once and consults this list.
_ACTIVE_WATCHERS: list["CompileWatcher"] = []
_LISTENER_STATE = {"installed": False}


def _install_listener() -> None:
    if _LISTENER_STATE["installed"]:
        return
    import jax.monitoring

    def on_duration(event: str, duration: float, fun_name: str = "", **kw: Any) -> None:
        if event == BACKEND_COMPILE_EVENT:
            for watcher in list(_ACTIVE_WATCHERS):
                watcher.record(duration, fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _LISTENER_STATE["installed"] = True


class CompileWatcher:
    """Count backend compile requests and their seconds while active.

    Used as a context manager around a run's round loop; ``poll`` after
    each round folds deltas into ``jit.compiles`` / ``jit.compile_time_s``
    counters and sets the ``jit.round_compiles`` gauge so a steady-state
    round recompiling shows up as a nonzero gauge.  With an enabled
    ``tracer`` each request is also a ``compile`` span on its own track,
    ending when jax reports it and carrying ``fun``, the program's name.
    """

    def __init__(self, metrics: MetricsRegistry | None, tracer: Tracer | None = None):
        self.metrics = metrics
        self.tracer = resolve_tracer(tracer)
        self.compiles = 0
        self.compile_time_s = 0.0
        self._polled_compiles = 0
        self._polled_time_s = 0.0

    def __enter__(self) -> "CompileWatcher":
        _install_listener()
        _ACTIVE_WATCHERS.append(self)
        return self

    def __exit__(self, *exc_info: object) -> None:
        if self in _ACTIVE_WATCHERS:
            _ACTIVE_WATCHERS.remove(self)

    def record(self, duration: float, fun_name: str) -> None:
        """One backend compile request of ``duration`` seconds, ending now."""
        self.compiles += 1
        self.compile_time_s += duration
        tracer = self.tracer
        if tracer.enabled:
            tracer.complete(
                "compile",
                start=tracer.now() - duration,
                dur=duration,
                track="compile",
                fun=str(fun_name),
            )

    def poll(self) -> int:
        """Fold deltas since the last poll into the registry; return delta."""
        delta = self.compiles - self._polled_compiles
        delta_t = self.compile_time_s - self._polled_time_s
        self._polled_compiles = self.compiles
        self._polled_time_s = self.compile_time_s
        if self.metrics is not None:
            if delta:
                self.metrics.counter("jit.compiles").inc(delta)
            if delta_t > 0:
                self.metrics.counter("jit.compile_time_s").inc(delta_t)
            self.metrics.gauge("jit.round_compiles").set(delta)
        return delta
