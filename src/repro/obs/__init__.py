"""repro.obs — dependency-free tracing, metrics and telemetry events.

The observability layer shared by all four engines (explore /
variability / transient / spice-lowered evaluation) and the solver
backend registry:

  * **span tracer** — ``with obs.trace("solve_dc"): ...`` /
    ``@obs.traced()``; thread-safe, nestable; exports Chrome
    ``trace_event`` JSON (`export_chrome_trace`) and a plain-text tree
    (`span_tree`), each span mirrored as a ``jax.profiler``
    annotation. JAX's compile events become build spans (``jit_trace``,
    ``jit_lower``, ``executable_fetch``, ``backend_compile``) with
    counters; `instrument_jit` splits jitted calls into ``[compile]``
    vs ``[run]`` spans by those events.
  * **metrics registry** — `counter` / `gauge` / `histogram` (fixed
    exponential buckets) with Prometheus text (`export_prometheus`) and
    JSON (`snapshot` / `export_json`) exporters.
  * **structured events** — `event("backend_fallback", cause=...)`
    counts occurrences, keeps an assertable event log (`events`), and
    marks the span timeline.

Everything is gated on one process-wide flag (`enable` / `disable` /
``REPRO_OBS=1``); when disabled, every entry point is a single flag
check returning shared no-op handles — zero allocations on the hot
path — and no listener is registered with JAX. See README § Observability.

The continuous-performance tier lives in submodules: `repro.obs.ledger`
(append-only JSONL run ledger), `repro.obs.regress` (noise-aware
regression verdicts against the ledger history), `repro.obs.prof`
(jax.profiler capture, device-memory watermarks, HLO cost analysis)
and `repro.obs.report` (``python -m repro.obs.report`` dashboard). See
README § Performance tracking.
"""
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    RESIDUAL_BUCKETS,
    SECONDS_BUCKETS,
    SNAPSHOT_QUANTILES,
    SWEEPS_BUCKETS,
    counter,
    event,
    events,
    export_json,
    export_prometheus,
    export_prometheus_file,
    exponential_buckets,
    gauge,
    histogram,
    quantile_from_cumulative,
    snapshot,
)
from repro.obs.metrics import reset as _reset_metrics
from repro.obs import state
from repro.obs.state import enabled
from repro.obs.trace import (
    Span,
    add_instant,
    chrome_trace,
    export_chrome_trace,
    instrument_jit,
    span_tree,
    spans,
    trace,
    traced,
)
from repro.obs.trace import listen as _listen
from repro.obs.trace import reset as _reset_traces
from repro.obs import ledger, prof, regress  # noqa: E402  (submodules)


def enable() -> None:
    """Turn observability on for the rest of the process (idempotent) and
    listen to JAX's compile events."""
    state.enable()
    _listen(True)


def disable() -> None:
    """Turn observability off and stop listening to JAX; already-recorded
    data is kept."""
    state.disable()
    _listen(False)


_listen(enabled())  # REPRO_OBS=1 listens from import on


def reset() -> None:
    """Drop all recorded spans, events and metrics (keeps the flag)."""
    _reset_traces()
    _reset_metrics()
    prof.reset_cost()


__all__ = [
    "DEFAULT_BUCKETS",
    "RESIDUAL_BUCKETS",
    "SECONDS_BUCKETS",
    "SNAPSHOT_QUANTILES",
    "SWEEPS_BUCKETS",
    "Span",
    "add_instant",
    "chrome_trace",
    "counter",
    "disable",
    "enable",
    "enabled",
    "event",
    "events",
    "export_chrome_trace",
    "export_json",
    "export_prometheus",
    "export_prometheus_file",
    "exponential_buckets",
    "gauge",
    "histogram",
    "instrument_jit",
    "ledger",
    "prof",
    "quantile_from_cumulative",
    "regress",
    "reset",
    "snapshot",
    "span_tree",
    "spans",
    "trace",
    "traced",
]
