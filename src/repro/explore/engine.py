"""Batched design-space exploration engine.

Takes an arbitrary list of (name, IMACConfig) points — usually from a
`SweepSpec` — and evaluates them by:

  1. memo lookup: points already in the `ResultCache` are returned
     without touching the solver;
  2. structural grouping: the remaining points are bucketed by
     `core.evaluate.structure_key` (partition plans, solver iteration
     schedule, neuron model, parasitics flag, dtype);
  3. batched solves: each bucket runs as ONE vmapped, jitted circuit
     simulation via `core.evaluate.evaluate_batch` — conductances and
     electrical scalars stacked along a leading config axis — instead of
     one re-traced, re-compiled solve per configuration.

On the paper's Table III x Table IV cross product (24 configurations,
6 structures) this replaces 24 XLA compilations with 6; see
benchmarks/sweep_bench.py for the measured wall-clock win.

Points whose configuration carries a `VariabilitySpec`
(`cfg.variability`, usually set by SweepSpec's `trials`/`sigma_rel`/
`fault_rate`/... axes) are Monte-Carlo reliability points: each expands
into T stacked trial entries (repro.variability.expand_trials) that join
its structure group's single batched solve, and collapses back into a
`ReliabilityReport` instead of a point `IMACResult`. A sweep over
C configurations x T trials therefore still compiles once per structure
group. Use `pareto.RELIABILITY_OBJECTIVES` to extract fronts over
accuracy quantiles / worst-case power instead of point values.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Union

import jax

from repro import obs
from repro.core.digital import Params
from repro.core.evaluate import (
    IMACResult,
    concat_mapped,
    evaluate_batch,
    lift_mapped,
    structure_key,
)
from repro.core.imac import IMACConfig
from repro.core.mapping import map_network
from repro.distributed.sweep import MeshPlan, as_mesh_plan, shard_put
from repro.explore.cache import (
    ResultCache,
    data_fingerprint,
    params_fingerprint,
    result_key,
)
from repro.explore.pareto import DEFAULT_OBJECTIVES, pareto_front
from repro.explore.spec import SweepSpec
from repro.transient.spec import TransientSpec
from repro.variability.engine import expand_trials, run_variability, trial_keys
from repro.variability.report import ReliabilityReport, summarize

SweepInput = Union[SweepSpec, Sequence]


@dataclasses.dataclass
class SweepResult:
    """One evaluated design point.

    `result` is an IMACResult for deterministic points, or a
    ReliabilityReport for Monte-Carlo points (cfg.variability set).
    """

    name: str
    config: IMACConfig
    result: "IMACResult | ReliabilityReport"
    cached: bool = False

    def __getattr__(self, attr):
        # Proxy IMACResult fields (accuracy, avg_power, latency, ...) so
        # pareto_front and report code can address points directly.
        if attr.startswith("_") or attr == "result":
            raise AttributeError(attr)
        return getattr(self.result, attr)


def _as_points(points: SweepInput) -> "list[tuple[str, IMACConfig]]":
    if isinstance(points, SweepSpec):
        return points.materialize()
    out = []
    for i, item in enumerate(points):
        if isinstance(item, IMACConfig):
            out.append((f"cfg{i}", item))
        else:
            name, cfg = item
            out.append((str(name), cfg))
    return out


def run_sweep(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    points: SweepInput,
    *,
    n_samples: Optional[int] = None,
    chunk: int = 256,
    cache: "ResultCache | str | None" = None,
    variation_key: Optional[jax.Array] = None,
    noise_key: Optional[jax.Array] = None,
    activation: str = "sigmoid",
    timing: "bool | TransientSpec | None" = None,
    shard: "MeshPlan | bool | int | None" = None,
    verbose: bool = False,
) -> "list[SweepResult]":
    """Evaluate a design-space sweep with batching and memoization.

    Args:
      params: trained digital weights/biases [(W, b), ...].
      x, y: evaluation data (digital units / integer labels).
      points: a SweepSpec, or a sequence of IMACConfig or (name, config).
        Points whose config carries a VariabilitySpec evaluate as
        Monte-Carlo reliability points (ReliabilityReport results); their
        trials — including read-noise draws when the resolved technology
        has read_noise_rel > 0 — derive from the spec's own seed,
        independent of `variation_key`/`noise_key`, so a point evaluated
        here matches a direct run_variability call exactly.
      n_samples: samples per evaluation (default: all of x).
      chunk: samples per jitted solve.
      cache: ResultCache instance, a directory path to open one, or None.
      variation_key / noise_key: Monte-Carlo draws shared by every
        deterministic point (paired comparison across the design space).
        Reliability points ignore both — see `points` above — so their
        cache entries survive changes to either.
      activation: digital reference activation.
      timing: timing mode — run every point through the batched transient
        co-simulation (repro.transient) so results report
        waveform-measured latency and integrated energy. True uses a
        default TransientSpec; a TransientSpec applies that one. Points
        that already carry cfg.transient keep their own spec. Pair with
        `pareto.TRANSIENT_OBJECTIVES` for energy-aware extraction.
      shard: execute each structure group's stacked solve sharded
        across a JAX device mesh — a `repro.distributed.sweep.MeshPlan`,
        True (all visible devices), an int (device count), or None.
        None falls back to the spec's own `SweepSpec.shard`. Groups
        schedule largest-first, the next group's tensors stage onto the
        mesh (double-buffered `device_put`) while the current one
        computes, and circuit-solve results (parasitics=True) are
        bitwise-identical to the unsharded engine — padding replicates
        a real config and the solver's convergence test is pmax'ed
        across shards, so trip counts match. (Ideal-MVM points keep
        bitwise predictions; their power agrees to ~1e-7 relative —
        the einsum's reduction order follows the local batch shape.)
        Read-noise Monte-Carlo points and the transient part of
        timing sweeps keep their unsharded path.
      verbose: print per-group progress lines.

    Returns:
      One SweepResult per point, in input order.
    """
    items = _as_points(points)
    if shard is None and isinstance(points, SweepSpec):
        shard = points.shard
    plan = as_mesh_plan(shard)
    mesh = plan.build() if plan is not None else None
    t_run0 = time.perf_counter()
    with obs.trace("run_sweep", {"points": len(items)}):
        if timing:
            tspec = timing if isinstance(timing, TransientSpec) else TransientSpec()
            items = [
                (
                    name,
                    cfg
                    if cfg.transient is not None
                    else dataclasses.replace(cfg, transient=tspec),
                )
                for name, cfg in items
            ]
        if isinstance(cache, str):
            cache = ResultCache(cache)
        topology = [params[0][0].shape[0]] + [w.shape[1] for w, _ in params]

        results: "list[Optional[SweepResult]]" = [None] * len(items)

        # 1. Memo lookup.
        keys: "list[Optional[str]]" = [None] * len(items)
        pending: "list[int]" = []
        with obs.trace("memo_lookup", {"points": len(items)}):
            if cache is not None:
                params_fp = params_fingerprint(params)
                data_fp = data_fingerprint(
                    x[: n_samples or x.shape[0]], y[: n_samples or y.shape[0]]
                )
                for i, (name, cfg) in enumerate(items):
                    # Reliability points draw everything from their spec's seed,
                    # so their results — and cache keys — are independent of the
                    # sweep-level Monte-Carlo keys.
                    is_mc = cfg.variability is not None
                    keys[i] = result_key(
                        cfg,
                        params_fp,
                        data_fp,
                        n_samples=n_samples,
                        chunk=chunk,
                        variation_key=None if is_mc else variation_key,
                        noise_key=None if is_mc else noise_key,
                        activation=activation,
                    )
                    hit = cache.get(keys[i])
                    if hit is not None:
                        results[i] = SweepResult(name, cfg, hit, cached=True)
                    else:
                        pending.append(i)
            else:
                pending = list(range(len(items)))

        # 2. Group the misses by traced structure.
        groups: "dict[tuple, list[int]]" = {}
        for i in pending:
            groups.setdefault(structure_key(topology, items[i][1]), []).append(i)

        # mapWB depends only on (tech, vdd, quantize) for fixed params, so a
        # sweep over P partitionings x T technologies needs T mappings, not
        # P*T — memoize across groups. Monte-Carlo points share the same memo
        # for their deterministic base mapping (variation is drawn per trial,
        # not via the sweep-wide variation_key).
        mapping_memo: dict = {}

        def _mapped(cfg: IMACConfig, tech=None, vkey=variation_key):
            tech = tech if tech is not None else cfg.resolved_tech()
            memo_key = (
                tech.name, tech.r_low, tech.r_high, tech.levels, tech.sigma_rel,
                cfg.vdd, cfg.quantize, vkey is None,
            )
            if memo_key not in mapping_memo:
                mapping_memo[memo_key] = map_network(
                    params,
                    tech,
                    v_unit=cfg.vdd,
                    quantize=cfg.quantize,
                    variation_key=vkey,
                )
            return mapping_memo[memo_key]

        # 3. One batched solve per group. Deterministic points contribute one
        # stacked entry each; Monte-Carlo points contribute their T trial
        # entries — all sharing the group's single compiled solve. Exception:
        # Monte-Carlo points whose resolved technology has read noise run
        # solo through run_variability so their per-trial noise draws depend
        # only on the spec's seed (not on the point's position in the stack)
        # — identical results to a direct run_variability call, and safe to
        # memoize across differently-composed sweeps.
        # Phase A — prepare every group host-side: expand Monte-Carlo
        # trials, build the stacked mapping, note solo points. Kept
        # separate from execution so the scheduler can reorder groups
        # and the stager can work one group ahead.
        prepared = []
        with obs.trace("prepare", {"groups": len(groups)}):
            for skey, idxs in groups.items():
                entry_cfgs, stacks, spans, solo = [], [], [], []
                for i in idxs:
                    cfg = items[i][1]
                    vspec = cfg.variability
                    if vspec is None:
                        entry_cfgs.append(cfg)
                        stacks.append(lift_mapped(_mapped(cfg)))
                        spans.append((i, 1, None))
                        continue
                    base_tech = vspec.resolve_tech(cfg.resolved_tech())
                    if base_tech.read_noise_rel > 0.0:
                        solo.append(i)
                        continue
                    # Degenerate spec: all trials identical -> one stacked entry,
                    # replicated back to T at summarize time.
                    collapse = (
                        vspec.trials > 1
                        and vspec.is_deterministic_for(cfg.resolved_tech())
                    )
                    tcfgs, tstacked = expand_trials(
                        params, cfg, vspec,
                        keys=trial_keys(vspec)[:1] if collapse else None,
                        base_mapped=_mapped(cfg, tech=base_tech, vkey=None),
                    )
                    entry_cfgs.extend(tcfgs)
                    stacks.append(tstacked)
                    spans.append((i, len(tcfgs), vspec))
                prepared.append({
                    "skey": skey,
                    "idxs": idxs,
                    "entry_cfgs": entry_cfgs,
                    "stacked": concat_mapped(stacks) if stacks else None,
                    "spans": spans,
                    "solo": solo,
                })

        # Scheduler: largest stacked batch first — the wide groups
        # saturate the mesh while the narrow tail drains quickly.
        if plan is not None and plan.largest_first:
            prepared.sort(key=lambda g: len(g["entry_cfgs"]), reverse=True)

        def _stage(group):
            # Double-buffered host→device staging: issue the (async)
            # device_put of the next group's stacked tensors while the
            # current group computes. Best-effort — non-divisible
            # groups stage replicated and evaluate_batch pads/shards
            # them itself; transient groups integrate unsharded, so
            # their tensors stay on the default device.
            if (
                plan is not None
                and plan.overlap
                and group["stacked"] is not None
                and group["entry_cfgs"][0].transient is None
            ):
                group = dict(
                    group,
                    stacked=[
                        dataclasses.replace(
                            m,
                            g_pos=shard_put(m.g_pos, mesh, plan.axis),
                            g_neg=shard_put(m.g_neg, mesh, plan.axis),
                            k=shard_put(m.k, mesh, plan.axis),
                        )
                        for m in group["stacked"]
                    ],
                )
            return group

        staged = iter(prepared)
        if plan is not None and plan.overlap:
            from repro.distributed.sweep import stage_pipeline

            staged = (g for _, g in stage_pipeline(prepared, _stage))

        # Phase B — one batched (optionally sharded) solve per group.
        for gi, group in enumerate(staged):
            skey, idxs = group["skey"], group["idxs"]
            entry_cfgs, spans, solo = (
                group["entry_cfgs"], group["spans"], group["solo"]
            )
            g_attrs = {"configs": len(idxs), "group": gi}
            if plan is not None:
                g_attrs["devices"] = plan.axis_size()
            with obs.trace(f"group[{gi}]", g_attrs) as g_span:
                t0 = time.perf_counter()
                g_span.set("stacked", len(entry_cfgs))
                g_span.set("solo", len(solo))
                batch = evaluate_batch(
                    params,
                    x,
                    y,
                    entry_cfgs,
                    n_samples=n_samples,
                    chunk=chunk,
                    variation_key=variation_key,
                    noise_key=noise_key,
                    activation=activation,
                    mapped_stacked=group["stacked"],
                    mesh_plan=plan,
                ) if entry_cfgs else []
                for i in solo:
                    name, cfg = items[i]
                    rep = run_variability(
                        params, x, y, cfg, cfg.variability,
                        n_samples=n_samples, chunk=chunk, activation=activation,
                    )
                    results[i] = SweepResult(name, cfg, rep, cached=False)
                    if cache is not None:
                        cache.put(keys[i], rep, name=name)
                if verbose:
                    dt = time.perf_counter() - t0
                    print(
                        f"[explore] group {gi + 1}/{len(groups)}: "
                        f"{len(idxs)} configs ({len(entry_cfgs)} stacked entries, "
                        f"{len(solo)} solo) in {dt:.2f}s (plans {skey[1]})"
                    )
                pos = 0
                for i, count, vspec in spans:
                    name, cfg = items[i]
                    if vspec is None:
                        res = batch[pos]
                    else:
                        trials = batch[pos : pos + count]
                        if count == 1 and vspec.trials > 1:  # collapsed degenerate
                            trials = trials * vspec.trials
                        res = summarize(trials, acc_threshold=vspec.acc_threshold)
                    pos += count
                    results[i] = SweepResult(name, cfg, res, cached=False)
                    if cache is not None:
                        cache.put(keys[i], res, name=name)

        elapsed = time.perf_counter() - t_run0
        derived = f"points={len(items)};groups={len(groups)}"
        if plan is not None:
            derived += f";mesh={plan.shape_str()}"
        # Opt-in perf-trajectory entry (obs enabled + REPRO_OBS_LEDGER
        # set): us/point with the metrics snapshot riding along. Sharded
        # runs tag their mesh shape so regression baselines stay
        # per-device-population (ledger.ENV_KEYS).
        with obs.ledger.mesh_context(
            plan.shape_str() if plan is not None else None
        ):
            obs.ledger.record_engine_run(
                "run_sweep",
                elapsed,
                count=len(items),
                derived=derived,
            )
        return [r for r in results if r is not None]


def explore(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    points: SweepInput,
    *,
    objectives=DEFAULT_OBJECTIVES,
    **kw,
) -> "tuple[list[SweepResult], list[SweepResult]]":
    """run_sweep + Pareto extraction: returns (all results, front)."""
    results = run_sweep(params, x, y, points, **kw)
    front = [results[i] for i in pareto_front(results, objectives)]
    return results, front
