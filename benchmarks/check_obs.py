"""CI assertion helper for the observability exports.

Parses a Prometheus text exposition and a Chrome trace_event JSON
produced by ``benchmarks.run --trace --metrics-out`` and asserts the
layer actually observed the run:

  * the Prometheus file parses (``# TYPE`` lines + ``name{labels} value``
    samples only) and contains the key series;
  * the trace is valid trace_event JSON with complete ("X") spans,
    including at least one compile-phase and one steady-state
    ``solve_chunk`` span and one ``jit_trace`` build span;
  * any additional arguments are ``BENCH_<name>.json`` payloads checked
    against the v2 schema (`validate_bench_payload`).

Usage: python -m benchmarks.check_obs METRICS.prom TRACE.json [BENCH.json...]
Exits non-zero with a message on the first missing invariant.
"""
from __future__ import annotations

import json
import re
import sys

#: v2 BENCH_<name>.json: required metadata keys and their types.
BENCH_REQUIRED = {
    "schema_version": int,
    "bench": str,
    "ok": bool,
    "git_sha": str,
    "jax_version": str,
    "jax_backend": str,
    "device_platform": str,
    "device_count": int,
    "python_version": str,
    "rows": list,
}

REQUIRED_SERIES = (
    "solver_sweeps",
    "cache_hits_total",
    "backend_fallback_total",
    "jit_traces_total",
)

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.e+-]+|\+Inf|NaN)$"
)


def parse_prometheus(text: str) -> "dict[str, list[str]]":
    """Parse a text exposition; returns {metric family: sample lines}.

    Raises ValueError on any line that is neither a comment nor a valid
    sample — the format check CI relies on.
    """
    families: "dict[str, list[str]]" = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip() or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            raise ValueError(f"line {lineno} is not a valid sample: {line!r}")
        name = m.group(1)
        # _bucket/_sum/_count samples belong to their histogram family.
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        families.setdefault(family, []).append(line)
        if name != family:
            families.setdefault(name, []).append(line)
    return families


def check_metrics(path: str) -> None:
    with open(path) as fh:
        families = parse_prometheus(fh.read())
    missing = [s for s in REQUIRED_SERIES if s not in families]
    if missing:
        raise SystemExit(
            f"metrics export {path} is missing key series: {missing}; "
            f"present: {sorted(k for k in families if '_bucket' not in k)}"
        )
    print(f"ok: {path} parses; key series present: {REQUIRED_SERIES}")


def check_trace(path: str) -> None:
    with open(path) as fh:
        trace = json.load(fh)
    events = trace["traceEvents"]
    complete = [e for e in events if e.get("ph") == "X"]
    if not complete:
        raise SystemExit(f"trace {path} has no complete ('X') spans")
    for e in complete:
        for field in ("name", "ts", "dur", "pid", "tid"):
            if field not in e:
                raise SystemExit(f"span missing {field!r}: {e}")
    names = {e["name"] for e in complete}
    if "solve_chunk[compile]" not in names:
        raise SystemExit(
            f"trace {path} has no solve_chunk[compile] span; got {sorted(names)}"
        )
    if "solve_chunk[run]" not in names:
        raise SystemExit(
            f"trace {path} has no steady-state solve_chunk[run] span; "
            f"got {sorted(names)}"
        )
    if "jit_trace" not in names:
        raise SystemExit(
            f"trace {path} has no jit_trace build span; got {sorted(names)}"
        )
    print(
        f"ok: {path} has {len(complete)} spans incl. compile/run "
        f"solve_chunk split and jit_trace build spans"
    )


def validate_bench_payload(payload: dict) -> None:
    """Assert a BENCH_<name>.json payload matches the v2 schema.

    Raises ValueError naming the first violated invariant. Additive
    keys (``git_dirty``, the embedded ``metrics`` snapshot) are allowed
    — the schema only pins what trend tooling depends on.
    """
    if not isinstance(payload, dict):
        raise ValueError(f"payload is {type(payload).__name__}, not an object")
    for key, typ in BENCH_REQUIRED.items():
        if key not in payload:
            raise ValueError(f"missing required key {key!r}")
        if not isinstance(payload[key], typ):
            raise ValueError(
                f"key {key!r} is {type(payload[key]).__name__}, "
                f"expected {typ.__name__}"
            )
    if payload["schema_version"] < 2:
        raise ValueError(
            f"schema_version {payload['schema_version']} < 2"
        )
    if not payload["git_sha"]:
        raise ValueError("git_sha is empty")
    for i, row in enumerate(payload["rows"]):
        if not isinstance(row, dict):
            raise ValueError(f"rows[{i}] is not an object")
        if not isinstance(row.get("name"), str) or not row["name"]:
            raise ValueError(f"rows[{i}] has no name")
        if not isinstance(row.get("us_per_call"), (int, float)) or isinstance(
            row.get("us_per_call"), bool
        ):
            raise ValueError(f"rows[{i}] us_per_call is not a number")
        if not isinstance(row.get("derived"), str):
            raise ValueError(f"rows[{i}] derived is not a string")
    metrics = payload.get("metrics")
    if metrics is not None:
        if not isinstance(metrics, dict):
            raise ValueError("metrics snapshot is not an object")
        for name, fam in metrics.items():
            if not isinstance(fam, dict) or "type" not in fam or (
                "series" not in fam
            ):
                raise ValueError(
                    f"metrics[{name!r}] lacks type/series"
                )


def check_bench_json(path: str) -> None:
    with open(path) as fh:
        payload = json.load(fh)
    try:
        validate_bench_payload(payload)
    except ValueError as e:
        raise SystemExit(f"bench payload {path} violates the v2 schema: {e}")
    print(f"ok: {path} matches the v2 BENCH schema "
          f"({len(payload['rows'])} rows)")


def main() -> None:
    if len(sys.argv) < 3:
        raise SystemExit(__doc__)
    check_metrics(sys.argv[1])
    check_trace(sys.argv[2])
    for path in sys.argv[3:]:
        check_bench_json(path)


if __name__ == "__main__":
    main()
