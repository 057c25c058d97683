"""Deep profiling hooks: jax.profiler capture, device-memory watermarks,
and per-jitted-fn HLO cost analysis.

Three opt-in layers on top of the base tracer/metrics:

  * `jax_profile(logdir)` — context manager wrapping
    ``jax.profiler.trace``; also armed by the
    ``REPRO_OBS_JAX_PROFILE`` environment variable so any entry point
    (benchmarks, engines, services) can capture a TensorBoard-loadable
    device profile without code changes.
  * `sample_memory(stage)` — device-memory gauges
    (``device_bytes_in_use`` / ``device_peak_bytes_in_use`` labeled by
    pipeline stage), sampled around the map/stamp/solve/measure stage
    spans. CPU backends without ``memory_stats()`` are a silent no-op.
  * `instrument_jit(fn, name)` — the tracer's compile-vs-run span split
    *plus*, when cost profiling is enabled (``REPRO_OBS_COST=1`` or
    `enable_cost`), a one-time HLO ``cost_analysis()`` per input
    signature recording ``hlo_flops`` / ``hlo_bytes_accessed`` gauges.
    Cost analysis relowers the function once per new signature, which
    is why it is opt-in.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable, Optional

from repro.obs import metrics, state

# The package re-exports the trace() *function* under the submodule's
# name, which shadows the attribute `import repro.obs.trace as _trace`
# would resolve — go through sys.modules to bind the module itself.
import importlib

_trace = importlib.import_module("repro.obs.trace")

#: Published per-chip peaks for roofline shares, keyed by
#: `jax.Device.device_kind`. Source: Google Cloud documentation, "TPU
#: v5e" (197 TFLOP/s bf16, 819 GB/s HBM). A TPU kind missing here is an
#: error, not a default; CPU and GPU hosts report no peak. Override the
#: FLOP/s peak with REPRO_OBS_PEAK_FLOPS (e.g. "1.97e14").
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

_cost_flag: "Optional[bool]" = None
_costs: "dict[str, dict]" = {}  # fn name -> last recorded cost dict
_cost_seen: "set[tuple]" = set()


def cost_enabled() -> bool:
    """Whether HLO cost analysis runs inside `instrument_jit`."""
    if _cost_flag is not None:
        return _cost_flag
    return os.environ.get("REPRO_OBS_COST", "").lower() not in (
        "", "0", "false", "no",
    )


def enable_cost() -> None:
    global _cost_flag
    _cost_flag = True


def disable_cost() -> None:
    global _cost_flag
    _cost_flag = False


def reset_cost() -> None:
    """Forget the flag override and every cached cost record."""
    global _cost_flag
    _cost_flag = None
    _costs.clear()
    _cost_seen.clear()


def device_peaks(kind: "Optional[str]" = None) -> "Optional[dict]":
    """Published peaks of a device kind (default: the first device).

    Returns None off-TPU. Raises KeyError for a TPU kind without a row
    in `DEVICE_PEAKS`.
    """
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    if kind in DEVICE_PEAKS:
        return DEVICE_PEAKS[kind]
    if kind.startswith("TPU"):
        raise KeyError(
            f"no published peaks for device kind {kind!r}; add a row to "
            "repro.obs.prof.DEVICE_PEAKS with its source"
        )
    return None


def peak_flops(kind: "Optional[str]" = None) -> "Optional[float]":
    """Peak device FLOP/s for utilization, or None off-TPU."""
    env = os.environ.get("REPRO_OBS_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    peaks = device_peaks(kind)
    return peaks["flops"] if peaks else None


@contextlib.contextmanager
def jax_profile(logdir: "Optional[str]" = None):
    """Capture a jax.profiler trace into `logdir` (TensorBoard format).

    `logdir` defaults to ``$REPRO_OBS_JAX_PROFILE``; when neither is
    set the context is a no-op, so call sites can wrap unconditionally.
    """
    logdir = logdir or os.environ.get("REPRO_OBS_JAX_PROFILE")
    if not logdir:
        yield None
        return
    import jax

    os.makedirs(logdir, exist_ok=True)
    with _trace.trace("jax_profile", {"logdir": logdir}):
        with jax.profiler.trace(logdir):
            yield logdir


def device_memory_stats() -> "Optional[dict]":
    """`memory_stats()` of the first local device, or None (CPU)."""
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return dict(stats)


def sample_memory(stage: str) -> "Optional[dict]":
    """Record device-memory watermark gauges for one pipeline stage.

    Gauges: ``device_bytes_in_use{stage=...}`` (live allocation at the
    sample point) and ``device_peak_bytes_in_use{stage=...}`` (the
    allocator's high-water mark). Returns the raw stats dict, or None
    when disabled or the backend exposes no memory stats.
    """
    if not state._enabled:
        return None
    stats = device_memory_stats()
    if stats is None:
        return None
    labels = {"stage": stage}
    if "bytes_in_use" in stats:
        metrics.gauge("device_bytes_in_use", labels).set(
            stats["bytes_in_use"]
        )
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        g = metrics.gauge("device_peak_bytes_in_use", labels)
        if peak > g.value:
            g.set(peak)
    return stats


def hlo_cost(jitfn: Callable, *args, **kw) -> "Optional[dict]":
    """FLOPs / bytes-accessed of `jitfn` at this signature via XLA.

    Lowers and compiles the jitted callable (AOT path — cached by jax
    per signature) and returns the normalized ``cost_analysis()`` dict:
    ``{"flops": float, "bytes_accessed": float, ...}``. Returns None
    when the backend implements no cost analysis or lowering fails
    (e.g. exotic custom calls).
    """
    try:
        cost = jitfn.lower(*args, **kw).compile().cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    out = {}
    for key, norm in (
        ("flops", "flops"),
        ("bytes accessed", "bytes_accessed"),
        ("transcendentals", "transcendentals"),
        ("optimal_seconds", "optimal_seconds"),
    ):
        if key in cost:
            try:
                out[norm] = float(cost[key])
            except (TypeError, ValueError):
                pass
    return out or None


def last_cost(name: str) -> "Optional[dict]":
    """The most recent cost record for an instrumented fn, or None."""
    return _costs.get(name)


def _sig_key(name: str, args, kw) -> tuple:
    import jax

    leaves = jax.tree_util.tree_leaves((args, kw))
    return (
        name,
        tuple(
            (getattr(v, "shape", None), str(getattr(v, "dtype", type(v))))
            for v in leaves
        ),
    )


def _record_cost(jitfn: Callable, name: str, args, kw) -> "Optional[dict]":
    cost = hlo_cost(jitfn, *args, **kw)
    if cost is None:
        return None
    _costs[name] = cost
    labels = {"fn": name}
    if "flops" in cost:
        metrics.gauge("hlo_flops", labels).set(cost["flops"])
    if "bytes_accessed" in cost:
        metrics.gauge("hlo_bytes_accessed", labels).set(
            cost["bytes_accessed"]
        )
    return cost


def instrument_jit(fn: Callable, name: str) -> Callable:
    """Span split + opt-in HLO cost record.

    Wraps `trace.instrument_jit` (``name[compile]`` / ``name[run]``
    spans) and, with cost profiling on, runs `hlo_cost` once per new
    input signature (gauges ``hlo_flops`` / ``hlo_bytes_accessed``).
    """
    traced_fn = _trace.instrument_jit(fn, name)

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        if not state._enabled:
            return fn(*args, **kw)
        out = traced_fn(*args, **kw)
        if cost_enabled():
            try:
                key = _sig_key(name, args, kw)
            except Exception:
                key = None
            if key is not None and key not in _cost_seen:
                _cost_seen.add(key)
                _record_cost(fn, name, args, kw)
        return out

    return wrapped
