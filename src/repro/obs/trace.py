"""Span tracer: nestable, thread-safe, exportable.

`trace(name)` is a context manager recording one wall-clock span;
`traced(name)` is the decorator form (the enabled check happens at call
time, so functions decorated while observability is off start tracing
as soon as it is enabled). Spans nest through a thread-local stack and
carry their parent id and depth, so the same records export both as
Chrome ``trace_event`` JSON (chrome://tracing, Perfetto) and as an
indented plain-text tree (`span_tree`). Each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so a profiler trace holds
the program's spans on its own clock.

Build spans come from JAX's own compile events (`jax.monitoring`),
through listeners that exist only while observability is enabled:
``jit_trace`` (tracing to a jaxpr), ``jit_lower`` (jaxpr to an MLIR
module), ``executable_fetch`` (a backend compile served by the
persistent compilation cache) and ``backend_compile`` (one that was
not). They are recorded when JAX reports them, under the span open on
the calling thread, nested by time; traces are also counted in
``jit_traces_total``.

`instrument_jit` wraps a ``jax.jit``-ed callable so every call records a
span named ``name[compile]`` when a backend compile or executable fetch
happened during the call and ``name[run]`` otherwise. It does not wait
for the device: the span covers building and enqueueing the program.

When observability is disabled (`repro.obs.state`), `trace` returns a
shared no-op handle: no span objects, no lock traffic, no allocations.
"""
from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time
from typing import Callable, Optional

from repro.obs import state

_lock = threading.Lock()
_spans: "list[Span]" = []          # finished spans, in completion order
_live: "dict[int, Span]" = {}      # open spans by sid (flushed on export)
_instants: "list[dict]" = []       # point-in-time marks (obs.event)
_ids = itertools.count(1)          # thread-safe under CPython
_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class Span:
    """One live-or-finished span. Use via ``with trace(name):``."""

    __slots__ = (
        "name", "attrs", "sid", "parent", "depth", "tid", "t_start", "t_end",
        "_annotation", "_kids",
    )

    def __init__(self, name: str, attrs: "Optional[dict]" = None):
        self.name = name
        self.attrs = attrs
        self.sid = 0
        self.parent: "Optional[int]" = None
        self.depth = 0
        self.tid = 0
        self.t_start = 0.0
        self.t_end = 0.0
        self._annotation = None
        self._kids: "Optional[list[Span]]" = None  # nested build spans

    def set(self, key: str, value) -> None:
        """Attach an attribute (exported in the Chrome trace ``args``)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start

    def __enter__(self) -> "Span":
        stack = _stack()
        self.sid = next(_ids)
        self.tid = threading.get_ident()
        if stack:
            top = stack[-1]
            self.parent = top.sid
            self.depth = top.depth + 1
        stack.append(self)
        with _lock:
            _live[self.sid] = self
        self._annotation = _annotation(self.name)
        self._annotation.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t_end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if exc_type is not None:
            # The exception keeps unwinding (we return False); the span
            # records what killed its body so failed stages are visible
            # in the exported trace instead of silently short.
            self.set("error", f"{exc_type.__name__}: {exc}")
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        with _lock:
            _live.pop(self.sid, None)
            _spans.append(self)
        return False


class _NoopSpan:
    """Shared disabled-mode handle: enter/exit/set are free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass

    @property
    def duration(self) -> float:
        return 0.0


_NOOP = _NoopSpan()


def trace(name: str, attrs: "Optional[dict]" = None):
    """Context manager recording one span (no-op when obs is disabled).

    Args:
      name: span label (dots/brackets render fine in both exporters).
      attrs: optional dict of attributes (Chrome trace ``args``).
    """
    if not state._enabled:
        return _NOOP
    return Span(name, attrs)


def traced(name: "Optional[str]" = None, attrs: "Optional[dict]" = None):
    """Decorator form of `trace`; checks the enable flag per call."""

    def deco(fn: Callable) -> Callable:
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            if not state._enabled:
                return fn(*args, **kw)
            with trace(label, dict(attrs) if attrs else None):
                return fn(*args, **kw)

        return wrapped

    return deco


def instrument_jit(fn: Callable, name: str) -> Callable:
    """Wrap a jitted callable with compile-vs-run split spans.

    Each call records ``name[compile]`` when JAX compiled an executable
    or fetched one from the persistent cache during the call, and
    ``name[run]`` otherwise; the build spans of the call nest under it.
    The span ends when the call returns, without waiting for the device.
    With observability disabled it forwards with one flag check.
    """

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        if not state._enabled:
            return fn(*args, **kw)
        before = _backend_builds()
        sp = Span(name)
        with sp:
            out = fn(*args, **kw)
            compiled = _backend_builds() > before
            sp.name = f"{name}[compile]" if compiled else f"{name}[run]"
            sp.set("compiled", compiled)
        return out

    return wrapped


# ---------------------------------------------------------------------------
# Build spans from JAX's compile events.
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: Build span names, in the order JAX builds a program.
BUILD_SPANS = ("jit_trace", "jit_lower", "executable_fetch", "backend_compile")

_listening = False
_annotation_cls = None


def _annotation(name: str):
    """A `jax.profiler.TraceAnnotation` for a span being entered."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation

        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


def _backend_builds() -> int:
    """Backend compiles and executable fetches seen on this thread."""
    return getattr(_local, "backend_builds", 0)


def _on_event(event: str, **_kw) -> None:
    # Fires inside the backend-compile event it belongs to.
    if event == _CACHE_HIT_EVENT:
        _local.cache_hit = True


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kw) -> None:
    if event == _TRACE_EVENT:
        name = "jit_trace"
    elif event == _LOWER_EVENT:
        name = "jit_lower"
    elif event == _BACKEND_EVENT:
        hit = getattr(_local, "cache_hit", False)
        name = "executable_fetch" if hit else "backend_compile"
        _local.cache_hit = False
        _local.backend_builds = _backend_builds() + 1
    else:
        return
    _record_build(name, end_time - start_time, kw.get("fun_name"))
    if name == "jit_trace":
        from repro.obs import metrics

        metrics.counter("jit_traces_total").inc()


def _record_build(name: str, seconds: float, fun_name) -> None:
    """Record a finished build step that JAX timed on its wall clock.

    It ends now on the span clock and started `seconds` earlier. Its
    parent is the span open on this thread. JAX times the steps with
    nested ``with`` blocks and reports each as it ends, inner steps
    first, so every earlier build span under the same parent that
    started inside this one is its child.
    """
    end = time.perf_counter()
    sp = Span(name, {"fun_name": fun_name} if fun_name is not None else None)
    sp.sid = next(_ids)
    sp.tid = threading.get_ident()
    sp.t_start, sp.t_end = end - seconds, end
    stack = _stack()
    top = stack[-1] if stack else None
    if top is not None:
        sp.parent, sp.depth = top.sid, top.depth + 1
    # Unadopted build spans by parent, in time order; only parents still
    # open can receive more.
    groups = getattr(_local, "build_roots", None)
    if groups is None:
        groups = _local.build_roots = {}
    roots = groups.get(sp.parent)
    if roots is None:
        open_ids = {s.sid for s in stack}
        for pid in [p for p in groups if p not in open_ids]:
            del groups[pid]
        roots = groups[sp.parent] = []
    # The children are the latest roots. Midpoints, so that the few
    # microseconds between JAX's clock reading and this callback cannot
    # turn a sibling into a child.
    kids = []
    while roots and (roots[-1].t_start + roots[-1].t_end) / 2 >= sp.t_start:
        kids.append(roots.pop())
    kids.reverse()
    for kid in kids:
        kid.parent = sp.sid
    sp._kids = kids or None
    bounds = [r.t_end for r in roots[-1:]]  # the previous sibling's end
    if top is not None:
        bounds.append(top.t_start)
    _fit(sp, max(bounds, default=sp.t_start), end, sp.depth)
    roots.append(sp)
    with _lock:
        _spans.append(sp)


def _fit(sp: Span, lo: float, hi: float, depth: int) -> None:
    """Clamp a build span into [lo, hi] and its children, one after the
    other, into it, so that no span's self time is negative."""
    sp.depth = depth
    sp.t_start = min(max(sp.t_start, lo), hi)
    sp.t_end = min(max(sp.t_end, sp.t_start), hi)
    t = sp.t_start
    for kid in sp._kids or ():
        _fit(kid, t, sp.t_end, depth + 1)
        t = kid.t_end


def listen(on: bool) -> None:
    """Register (or unregister) the build-event listeners with JAX."""
    global _listening
    if on == _listening:
        return
    from jax import monitoring

    if on:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_time_span_listener(_on_time_span)
    else:
        monitoring.unregister_event_listener(_on_event)
        monitoring.unregister_event_time_span_listener(_on_time_span)
    _listening = on


def add_instant(name: str, attrs: "Optional[dict]" = None) -> None:
    """Record a point-in-time mark on the trace timeline (obs.event)."""
    if not state._enabled:
        return
    rec = {
        "name": name,
        "ts": time.perf_counter(),
        "tid": threading.get_ident(),
        "attrs": dict(attrs) if attrs else {},
    }
    with _lock:
        _instants.append(rec)


def spans() -> "list[Span]":
    """Snapshot of the finished spans recorded so far."""
    with _lock:
        return list(_spans)


def live_spans() -> "list[Span]":
    """Snapshot of the spans currently open (entered, not yet exited)."""
    with _lock:
        return list(_live.values())


def reset() -> None:
    """Drop all recorded spans and instant marks (open spans too)."""
    with _lock:
        _spans.clear()
        _live.clear()
        _instants.clear()
    _local.build_roots = {}


# ---------------------------------------------------------------------------
# Exporters.
# ---------------------------------------------------------------------------


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def chrome_trace() -> dict:
    """The recorded spans as a Chrome ``trace_event`` JSON object.

    Loadable by chrome://tracing and https://ui.perfetto.dev. Spans are
    complete ('X') events with microsecond timestamps; instant marks
    ('i') carry their fields as args. Still-open spans are flushed as
    complete events truncated at export time and tagged
    ``unfinished=true`` — a crash mid-pipeline must not drop the very
    spans that show where it died. Timestamps are rebased to the
    earliest recorded event so the trace starts near t=0.
    """
    now = time.perf_counter()
    with _lock:
        done = list(_spans)
        open_ = list(_live.values())
        marks = list(_instants)
    t0 = min(
        [s.t_start for s in done + open_] + [m["ts"] for m in marks],
        default=0.0,
    )
    pid = os.getpid()
    events = [
        {
            "name": s.name,
            "cat": "repro",
            "ph": "X",
            "ts": (s.t_start - t0) * 1e6,
            "dur": (s.t_end - s.t_start) * 1e6,
            "pid": pid,
            "tid": s.tid,
            "args": {k: _jsonable(v) for k, v in (s.attrs or {}).items()},
        }
        for s in done
    ]
    events += [
        {
            "name": s.name,
            "cat": "repro",
            "ph": "X",
            "ts": (s.t_start - t0) * 1e6,
            "dur": (now - s.t_start) * 1e6,
            "pid": pid,
            "tid": s.tid,
            "args": {
                **{k: _jsonable(v) for k, v in (s.attrs or {}).items()},
                "unfinished": True,
            },
        }
        for s in open_
    ]
    events += [
        {
            "name": m["name"],
            "cat": "repro",
            "ph": "i",
            "s": "t",
            "ts": (m["ts"] - t0) * 1e6,
            "pid": pid,
            "tid": m["tid"],
            "args": {k: _jsonable(v) for k, v in m["attrs"].items()},
        }
        for m in marks
    ]
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str) -> str:
    """Write `chrome_trace()` to `path`; returns the path."""
    with open(path, "w") as fh:
        json.dump(chrome_trace(), fh, indent=1)
        fh.write("\n")
    return path


def span_tree() -> str:
    """The recorded spans as an indented plain-text tree (per thread)."""
    with _lock:
        done = sorted(_spans, key=lambda s: (s.tid, s.t_start, s.sid))
    if not done:
        return "(no spans recorded)"
    lines = []
    threads = sorted({s.tid for s in done})
    for tid in threads:
        if len(threads) > 1:
            lines.append(f"thread {tid}")
        for s in done:
            if s.tid != tid:
                continue
            extra = ""
            if s.attrs:
                extra = " " + " ".join(
                    f"{k}={_jsonable(v)}" for k, v in s.attrs.items()
                )
            lines.append(
                f"{'  ' * s.depth}{s.name:<40s} "
                f"{(s.t_end - s.t_start) * 1e3:10.2f} ms{extra}"
            )
    return "\n".join(lines)
