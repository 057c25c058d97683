"""testIMAC — Module 1 / Algorithm 1: deploy a trained DNN on an IMAC
configuration and report error rate, average power and latency.

The paper loops SPICE once per test sample (Algorithm 1 line 3); here the
whole test set is one batched, jitted circuit solve — same semantics,
TPU-native execution. Chunking keeps peak memory bounded for large
N_S x tiles products.

`evaluate_batch` is the functional core: it evaluates a *batch of
structurally-compatible configurations* in a single vmapped circuit solve
by stacking each configuration's conductance matrices and electrical
scalars along a leading axis. The single-config path (`test_imac`) and the
design-space engine (repro.explore) both go through it; the engine groups
arbitrary configuration lists into compatible batches via `structure_key`.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.obs import prof as obs_prof
from repro.core.backends import get_backend
from repro.core.digital import Params, mlp_forward
from repro.core.imac import (
    IMACConfig,
    build_plans,
    layer_latency,
    linear_forward,
    matrix_forward,
    matrix_plan,
)
from repro.core.mapping import MappedLayer, map_matrix, map_network
from repro.core.moe import SHARED, MoESpec, bucket, dispatch, route
from repro.core.neurons import NeuronModel
from repro.core.partition import PartitionPlan, ordered_sum, tile_matrix
from repro.core.solver import CircuitParams, SolveOptions, suggest_iters
from repro.distributed.sweep import (
    MeshPlan,
    pad_count,
    pad_stacked,
    stacked_spec,
)


class IMACResult(NamedTuple):
    accuracy: float
    error_rate: float
    avg_power: float          # W, averaged over samples (paper's P_average)
    latency: float            # s, settling + sampling (waveform-measured
                              # when cfg.transient is set, else analytic)
    digital_accuracy: float   # reference accuracy of the float model
    per_layer_power: tuple    # W per layer (batch mean)
    worst_residual: float     # solver convergence check
    n_samples: int
    hp: tuple
    vp: tuple
    # Energy per inference (J). Waveform-integrated over the transient
    # horizon when cfg.transient is set; otherwise the avg_power x
    # latency estimate, so energy-aware Pareto objectives
    # (explore.pareto.TRANSIENT_OBJECTIVES) work on mixed sweeps.
    energy: float = 0.0
    # The input-independent Elmore estimate, always reported — the
    # crossvalidation path compares it against the measured latency.
    latency_analytic: float = 0.0
    latency_source: str = "analytic"   # 'analytic' | 'transient'
    settled: bool = True               # waveform in band at the horizon

    # Degenerate-distribution aliases: a deterministic evaluation is a
    # single-trial Monte-Carlo run (every accuracy quantile collapses to
    # the point value, worst-case power is the only power). These mirror
    # repro.variability.ReliabilityReport's fields so mixed
    # deterministic + Monte-Carlo sweeps share Pareto objectives
    # (explore.pareto.RELIABILITY_OBJECTIVES) and report code.
    @property
    def n_trials(self) -> int:
        return 1

    @property
    def acc_mean(self) -> float:
        return self.accuracy

    @property
    def acc_std(self) -> float:
        return 0.0

    @property
    def acc_min(self) -> float:
        return self.accuracy

    @property
    def acc_max(self) -> float:
        return self.accuracy

    @property
    def acc_q05(self) -> float:
        return self.accuracy

    @property
    def acc_q25(self) -> float:
        return self.accuracy

    @property
    def acc_q50(self) -> float:
        return self.accuracy

    @property
    def acc_q75(self) -> float:
        return self.accuracy

    @property
    def acc_q95(self) -> float:
        return self.accuracy

    @property
    def power_mean(self) -> float:
        return self.avg_power

    @property
    def power_worst(self) -> float:
        return self.avg_power


def structure_key(topology: Sequence[int], cfg: IMACConfig) -> tuple:
    """Hashable key of everything that shapes the traced computation.

    Two configurations with equal keys differ only in *numeric leaves*
    (device conductances, wire/periphery resistances, SOR factor, read
    noise) and can therefore share one compiled, vmapped solve — the
    leaves are stacked along a leading config axis. Everything that
    changes array shapes (partition plans), loop bounds (`gs_iters`,
    `gs_tol`) or traced-code structure (parasitics, neuron model) must
    match.
    """
    plans = build_plans(topology, cfg)
    iters = tuple(
        cfg.gs_iters or suggest_iters(p.rows, p.cols) for p in plans
    )
    return (
        tuple(topology),
        tuple((p.hp, p.vp, p.rows, p.cols) for p in plans),
        bool(cfg.parasitics),
        iters,
        float(cfg.gs_tol),
        cfg.resolved_neuron(),
        jnp.dtype(cfg.dtype).name,
        # The transient spec shapes the traced scan (step count, method,
        # GS budget, horizon...), so configurations only batch together
        # when they request the same one (or none).
        cfg.transient,
    )


def lift_mapped(mapped: "Sequence[MappedLayer]") -> "list[MappedLayer]":
    """One configuration's mapping as a leading-axis-1 stacked mapping
    (the `mapped_stacked` form of `evaluate_batch`)."""
    return [
        dataclasses.replace(
            m,
            g_pos=m.g_pos[None],
            g_neg=m.g_neg[None],
            k=jnp.asarray([m.k]),
        )
        for m in mapped
    ]


def concat_mapped(
    stacks: "Sequence[Sequence[MappedLayer]]",
) -> "list[MappedLayer]":
    """Concatenate stacked mappings along the leading config axis.

    Each element of `stacks` is a per-layer list of MappedLayer whose
    arrays carry a leading (C_i,) axis (see `lift_mapped` /
    repro.variability.expand_trials); the result stacks sum(C_i) entries.
    """
    stacks = list(stacks)
    if len(stacks) == 1:
        return list(stacks[0])
    n_layers = len(stacks[0])
    return [
        dataclasses.replace(
            stacks[0][layer],
            g_pos=jnp.concatenate([s[layer].g_pos for s in stacks]),
            g_neg=jnp.concatenate([s[layer].g_neg for s in stacks]),
            k=jnp.concatenate(
                [jnp.atleast_1d(jnp.asarray(s[layer].k)) for s in stacks]
            ),
        )
        for layer in range(n_layers)
    ]


def stack_mapped(
    mapped_all: "Sequence[Sequence[MappedLayer]]", dtype
) -> "tuple[tuple, tuple, tuple]":
    """Stack per-config mapWB outputs along a leading config axis.

    Returns per-layer tuples (g_pos, g_neg, k): (C, fan_in+1, fan_out)
    conductances and (C,) sense scales — the stacked form both
    `evaluate_batch` and the transient engine consume.
    """
    n_layers = len(mapped_all[0])
    g_pos = tuple(
        jnp.stack([m[layer].g_pos for m in mapped_all])
        for layer in range(n_layers)
    )
    g_neg = tuple(
        jnp.stack([m[layer].g_neg for m in mapped_all])
        for layer in range(n_layers)
    )
    k = tuple(
        jnp.asarray([m[layer].k for m in mapped_all], dtype)
        for layer in range(n_layers)
    )
    return g_pos, g_neg, k


@dataclasses.dataclass(frozen=True)
class _GroupProgram:
    """Everything a group program's body reads besides its array arguments.

    `forward_all` reads its static inputs from this one value and from no
    closure, so equal programs trace to the same computation: it is the
    program cache's key. `solve_options.backend` holds the resolved
    backend, never None, so a later change of $REPRO_SOLVER_BACKEND or of
    the registry builds a new program.
    """

    plans: "tuple[PartitionPlan, ...]"
    iters: "tuple[int, ...]"
    tol: float
    v_unit: float
    neuron: NeuronModel
    parasitics: bool
    dtype: jnp.dtype
    noise_per_config: bool
    solve_options: SolveOptions


def forward_all(prog: _GroupProgram, gp, gn, kk, sc, xb, nkey):
    """Forward every stacked configuration over a chunk of samples.

    The config axis is an ordinary leading batch axis: each layer is
    ONE crossbar solve over (C, batch, tiles) with per-config
    electrical scalars broadcast inside the solver — a single
    while_loop, no per-lane masking.
    """
    n_layers = len(prog.plans)
    a = xb  # (batch, F); becomes (C, batch, F) after the first layer.
    keys = (
        jax.random.split(nkey, n_layers)
        if nkey is not None
        else [None] * n_layers
    )
    powers, residuals, sweeps = [], [], []
    for layer, plan in enumerate(prog.plans):
        cp = CircuitParams(
            r_row=sc["r_seg"],
            r_col=sc["r_seg"],
            r_source=sc["r_source"],
            r_tia=sc["r_tia"],
            gs_iters=prog.iters[layer],
            omega=sc["omega"],
            tol=prog.tol,
        )
        a, power, residual, _, swp = linear_forward(
            gp[layer],
            gn[layer],
            kk[layer],
            prog.v_unit,
            plan,
            cp,
            prog.neuron,
            a,
            parasitics=prog.parasitics,
            is_output=(layer == n_layers - 1),
            solve_options=prog.solve_options,
            noise_key=keys[layer],
            read_noise_rel=sc["read_noise"],
            noise_per_config=prog.noise_per_config,
            dtype=prog.dtype,
        )
        powers.append(ordered_sum(power) / power.shape[-1])  # (C,)
        residuals.append(residual)                # (C,)
        sweeps.append(swp)                        # scalar per layer
    pred = jnp.argmax(a, axis=-1)                 # (C, batch)
    return (
        pred,
        jnp.stack(powers, axis=-1),
        jnp.stack(residuals, axis=-1),
        jnp.stack(sweeps),                        # (L,)
    )


def forward_nokey(prog: _GroupProgram, gp, gn, kk, sc, xb):
    """`forward_all` without read noise (the sharded path's signature)."""
    return forward_all(prog, gp, gn, kk, sc, xb, None)


#: Group programs kept per process, least recently used evicted first.
PROGRAM_CACHE_SIZE = 64

_programs: "collections.OrderedDict[tuple, object]" = collections.OrderedDict()
_programs_lock = threading.Lock()


def clear_program_cache() -> None:
    """Forget every cached group program (the next calls build anew)."""
    with _programs_lock:
        _programs.clear()


def _bind(fn, prog: _GroupProgram):
    """`fn` with its program bound, under fn's name (the jitted module's)."""
    bound = functools.partial(fn, prog)
    bound.__name__ = fn.__name__
    return bound


def _build_run_chunk(prog: _GroupProgram, noisy: bool, shard):
    """The instrumented, jitted chunk solve of one group program.

    `shard` is None, or (mesh, axis, stacked_specs) for the shard_map
    path. prof.instrument_jit = the tracer's compile-vs-run span split
    plus opt-in HLO cost analysis (hlo_flops / hlo_bytes_accessed).
    """
    if shard is None:
        return obs_prof.instrument_jit(
            jax.jit(_bind(forward_all, prog)), "solve_chunk"
        )
    mesh, axis, stacked_specs = shard
    # Samples and the shared noise key replicate; the per-config outputs
    # (pred, powers, residuals) concatenate back along the config axis,
    # while the sweep counts — identical on every shard thanks to the
    # global-pmax cond — come out replicated.
    out_specs = (P(axis), P(axis), P(axis), P())
    if noisy:
        return obs_prof.instrument_jit(
            jax.jit(jax.shard_map(
                _bind(forward_all, prog),
                mesh=mesh,
                in_specs=stacked_specs + (P(), P()),
                out_specs=out_specs,
                check_vma=False,
            )),
            "solve_chunk",
        )
    inner = obs_prof.instrument_jit(
        jax.jit(jax.shard_map(
            _bind(forward_nokey, prog),
            mesh=mesh,
            in_specs=stacked_specs + (P(),),
            out_specs=out_specs,
            check_vma=False,
        )),
        "solve_chunk",
    )

    def run_chunk(gp, gn, kk, sc, xb, nk):
        return inner(gp, gn, kk, sc, xb)

    return run_chunk


def _run_chunk_for(prog: _GroupProgram, noisy: bool, shard):
    """The group program's chunk solve, reused when the process has it.

    A reused jit object serves a repeat call from JAX's own dispatch
    cache: no trace, no lowering, no executable fetch. Returns
    (run_chunk, lookup), lookup "hit", "miss" or "bypass" (a key that
    does not hash, e.g. an unhashable custom backend: built as before,
    not kept).
    """
    key = (prog, noisy, None)
    if shard is not None:
        mesh, axis, stacked_specs = shard
        leaves, tree = jax.tree_util.tree_flatten(stacked_specs)
        key = (prog, noisy, (mesh, axis, tuple(leaves), tree))
    return _cached_program(key, lambda: _build_run_chunk(prog, noisy, shard))


def _cached_program(key, build):
    """(program, lookup): the process's program under `key`, else `build()`
    kept under it; lookup "hit", "miss" or "bypass" (a key that does not
    hash: built, not kept). Counted in `program_cache_lookups_total`."""
    try:
        hash(key)
    except TypeError:
        lookup, program = "bypass", build()
    else:
        with _programs_lock:
            program = _programs.get(key)
            if program is not None:
                _programs.move_to_end(key)
                lookup = "hit"
        if program is None:
            lookup, program = "miss", build()
            with _programs_lock:
                _programs[key] = program
                while len(_programs) > PROGRAM_CACHE_SIZE:
                    _programs.popitem(last=False)
    obs.counter("program_cache_lookups_total", {"result": lookup}).inc()
    return program, lookup


def evaluate_batch(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    cfgs: "Sequence[IMACConfig]",
    *,
    n_samples: Optional[int] = None,
    chunk: int = 256,
    variation_key: Optional[jax.Array] = None,
    noise_key: Optional[jax.Array] = None,
    noise_per_config: bool = False,
    activation: str = "sigmoid",
    mapped: Optional[list] = None,
    mapped_stacked: Optional[list] = None,
    solve_options: Optional[SolveOptions] = None,
    mesh_plan: Optional[MeshPlan] = None,
) -> "list[IMACResult]":
    """Evaluate many structurally-compatible IMAC configurations at once.

    All configurations must share a `structure_key` (same partition plans,
    solver iteration counts, neuron model, parasitics flag, dtype); their
    conductance matrices and electrical scalars are stacked along a
    leading axis and the whole circuit simulation runs as one vmapped,
    jitted solve per sample chunk — one XLA compilation for the entire
    group instead of one per configuration. Later calls with the same
    group program (see `_GroupProgram`) reuse the jitted solve from a
    per-process cache and trace nothing (`clear_program_cache` empties it).

    When the configurations carry a `TransientSpec` (cfg.transient —
    identical across the batch by structure_key), the same stacked
    tensors additionally run through ONE batched time-domain integration
    (repro.transient), and the returned results report waveform-measured
    `latency` and integrated `energy` (latency_source='transient')
    instead of the analytic Elmore estimate, which stays available as
    `latency_analytic`.

    Args:
      params: trained digital weights/biases [(W, b), ...].
      x: (N, fan_in) inputs in [0, 1] digital units.
      y: (N,) integer labels.
      cfgs: structurally-compatible configurations (see `structure_key`).
      n_samples: N_S — number of test samples (default: all).
      chunk: samples per jitted circuit solve.
      variation_key: optional device-variation Monte-Carlo draw (the same
        draw is applied to every configuration, as in a paired sweep).
      noise_key: optional read-noise draw (shared across configurations
        unless `noise_per_config`).
      noise_per_config: draw read noise independently per stacked
        configuration instead of sharing one draw — used by the
        Monte-Carlo reliability engine (repro.variability), where each
        stacked entry is an independent trial.
      activation: digital reference activation.
      mapped: optional pre-computed mapWB output per configuration (one
        map_network list per config); lets a sweep engine share mappings
        between configurations that differ only in circuit parameters.
      mapped_stacked: optional pre-STACKED mapping — one MappedLayer per
        layer whose g_pos/g_neg carry a leading (C,) config axis and
        whose k is a (C,) array. Bypasses the per-config stacking below;
        the Monte-Carlo engine samples its trials directly in this form
        (see repro.variability) so trial tensors are never materialized
        twice. Mutually exclusive with `mapped`.
      solve_options: circuit-solver backend selection
        (`core.solver.SolveOptions`); None = the process default
        ($REPRO_SOLVER_BACKEND, else "scan").
      mesh_plan: shard the stacked config axis across a device mesh
        (`repro.distributed.sweep.MeshPlan`). The batch is padded to a
        multiple of the mesh axis by replicating entry 0 and the chunk
        solve runs under `shard_map` with a cross-shard convergence
        pmax — the circuit-solve path (parasitics=True) is
        bitwise-identical to the unsharded batch; the ideal-MVM path
        (parasitics=False) reproduces predictions/accuracy bitwise but
        its power einsum is shape-sensitive on XLA CPU, so power agrees
        only to float32 reassociation (~1e-7 relative).
        Falls back to single-device execution when the batch is smaller
        than `mesh_plan.min_group` or when per-config read-noise draws
        (`noise_per_config` with a `noise_key`) would change under
        sharding. The transient integration (cfg.transient) always runs
        unsharded. None = no sharding (default).

    Returns:
      One IMACResult per configuration, in input order.

    Raises:
      ValueError: if the configurations are not structurally compatible.
        Use repro.explore.run_sweep to group arbitrary configuration
        lists into compatible batches automatically.
    """
    cfgs = list(cfgs)
    if not cfgs:
        return []
    with obs.trace("evaluate_batch", {"configs": len(cfgs)}):
        return _evaluate_batch(
            params,
            x,
            y,
            cfgs,
            n_samples=n_samples,
            chunk=chunk,
            variation_key=variation_key,
            noise_key=noise_key,
            noise_per_config=noise_per_config,
            activation=activation,
            mapped=mapped,
            mapped_stacked=mapped_stacked,
            solve_options=solve_options,
            mesh_plan=mesh_plan,
        )


def _resolve_shard(mesh_plan, n_cfgs, noise_per_config, noise_key):
    """(mesh, axis, n_shards) when the batch should shard, else None.

    Per-config read-noise draws (`noise_per_config` + a key) depend on
    the full stacked shape — a shard would re-draw per local lane and
    diverge from the unsharded batch, so those fall back (recorded as a
    `shard_fallback` event).
    """
    if mesh_plan is None:
        return None
    if noise_per_config and noise_key is not None:
        obs.event("shard_fallback", cause="noise_per_config")
        return None
    if n_cfgs < mesh_plan.min_group:
        return None
    mesh = mesh_plan.build()
    return mesh, mesh_plan.axis, mesh.shape[mesh_plan.axis]


def _resolved_options(solve_options: Optional[SolveOptions]) -> SolveOptions:
    """`solve_options` with its backend resolved, as a program key holds it."""
    if solve_options is None:
        solve_options = SolveOptions()
    if solve_options.backend is None or isinstance(solve_options.backend, str):
        solve_options = dataclasses.replace(
            solve_options, backend=get_backend(solve_options.backend)
        )
    return solve_options


def _evaluate_batch(
    params,
    x,
    y,
    cfgs,
    *,
    n_samples,
    chunk,
    variation_key,
    noise_key,
    noise_per_config,
    activation,
    mapped,
    mapped_stacked,
    solve_options,
    mesh_plan=None,
) -> "list[IMACResult]":
    """`evaluate_batch` body (the wrapper holds the root span).

    Stage spans follow the pipeline: map (mapWB / stacking) → stamp
    (electrical-scalar assembly) → solve (chunked circuit solves, with
    compile-vs-run split via obs.instrument_jit) → device_wait (host
    blocked on the chunks' outputs; only while observability is on) →
    measure (host-side reduction to IMACResults + solver-telemetry
    histograms).
    """
    topology = [params[0][0].shape[0]] + [w.shape[1] for w, _ in params]
    key0 = structure_key(topology, cfgs[0])
    for c in cfgs[1:]:
        if structure_key(topology, c) != key0:
            raise ValueError(
                "evaluate_batch needs structurally-compatible configs "
                "(equal structure_key); got a mismatch — group them with "
                "repro.explore.run_sweep instead"
            )

    cfg0 = cfgs[0]
    plans = build_plans(topology, cfg0)
    neuron = cfg0.resolved_neuron()
    dtype = cfg0.dtype
    parasitics = cfg0.parasitics
    tol = cfg0.gs_tol
    v_unit = cfg0.vdd
    iters = [cfg0.gs_iters or suggest_iters(p.rows, p.cols) for p in plans]
    n_layers = len(plans)

    n = n_samples or x.shape[0]
    x, y = x[:n], y[:n]

    # mapWB per configuration (outside the trace, identical to the
    # single-config path), then stack: per layer (C, M, N) conductances
    # and (C,) sense scales; electrical scalars as (C,) vectors.
    with obs.trace("map", {"layers": n_layers}):
        if mapped_stacked is not None:
            if mapped is not None:
                raise ValueError(
                    "pass either mapped or mapped_stacked, not both"
                )
            for m in mapped_stacked:
                if m.g_pos.shape[0] != len(cfgs):
                    raise ValueError(
                        f"mapped_stacked leading axis {m.g_pos.shape[0]} != "
                        f"{len(cfgs)} configurations"
                    )
            g_pos = tuple(m.g_pos for m in mapped_stacked)
            g_neg = tuple(m.g_neg for m in mapped_stacked)
            k = tuple(jnp.asarray(m.k, dtype) for m in mapped_stacked)
        else:
            mapped_all = mapped if mapped is not None else [
                map_network(
                    params,
                    c.resolved_tech(),
                    v_unit=c.vdd,
                    quantize=c.quantize,
                    variation_key=variation_key,
                )
                for c in cfgs
            ]
            g_pos, g_neg, k = stack_mapped(mapped_all, dtype)
    obs_prof.sample_memory("map")
    with obs.trace("stamp"):
        scal = dict(
            r_seg=jnp.asarray(
                [c.interconnect.r_segment for c in cfgs], dtype
            ),
            r_source=jnp.asarray([c.r_source for c in cfgs], dtype),
            r_tia=jnp.asarray([c.r_tia for c in cfgs], dtype),
            omega=jnp.asarray([c.sor_omega for c in cfgs], dtype),
            read_noise=jnp.asarray(
                [c.resolved_tech().read_noise_rel for c in cfgs], dtype
            ),
        )

    # Waveform-accurate timing/energy: the whole stacked configuration
    # batch (sweep points or Monte-Carlo trials) integrates as ONE
    # batched transient — structure_key guarantees a shared spec.
    tspec = cfg0.transient
    transient_res = None
    if tspec is not None:
        from repro.transient.engine import network_transient_stacked

        if not parasitics:
            raise ValueError(
                "cfg.transient needs parasitics=True (the node "
                "capacitances live on the parasitic wire grid)"
            )
        tr_scal = dict(
            scal,
            c_seg=jnp.asarray(
                [c.interconnect.c_segment for c in cfgs], dtype
            ),
            t_samp=jnp.asarray([c.t_sampling for c in cfgs], dtype),
        )
        with obs.trace("transient", {"n_probe": tspec.n_probe}):
            transient_res = network_transient_stacked(
                g_pos, g_neg, k, tr_scal, plans, neuron, tspec,
                jnp.asarray(x[: tspec.n_probe], dtype), v_unit, iters, tol,
                dtype=dtype, solve_options=solve_options,
            )

    # The group program holds the backend itself (see _GroupProgram).
    solve_options = _resolved_options(solve_options)

    # Sharded execution: pad the stacked config axis to a multiple of
    # the mesh axis (replicating entry 0 — trip-count-neutral, see
    # distributed/sweep.pad_stacked) and place every stacked tensor with
    # its `config`-axis sharding. Pre-staged inputs (explore's
    # double-buffered shard_put) pass through as no-ops here.
    c_real = len(cfgs)
    shard = _resolve_shard(mesh_plan, c_real, noise_per_config, noise_key)
    if shard is not None:
        s_mesh, s_axis, n_shards = shard
        c_pad = pad_count(c_real, n_shards)
        with obs.trace(
            "shard_stage", {"devices": n_shards, "pad": c_pad - c_real}
        ):
            def _stage(t):
                t = pad_stacked(jnp.asarray(t), n_shards)
                return jax.device_put(
                    t, NamedSharding(s_mesh, stacked_spec(t, s_mesh, s_axis))
                )

            g_pos = tuple(_stage(t) for t in g_pos)
            g_neg = tuple(_stage(t) for t in g_neg)
            k = tuple(_stage(t) for t in k)
            scal = {name: _stage(v) for name, v in scal.items()}
        # The tol early-exit must see the *global* residual max inside
        # shard_map; shard_axis routes a lax.pmax into the cond.
        solve_options = dataclasses.replace(solve_options, shard_axis=s_axis)

    obs_prof.sample_memory("stamp")

    prog = _GroupProgram(
        plans=tuple(plans),
        iters=tuple(iters),
        tol=tol,
        v_unit=v_unit,
        neuron=neuron,
        parasitics=parasitics,
        dtype=jnp.dtype(dtype),
        noise_per_config=noise_per_config,
        solve_options=solve_options,
    )
    shard_specs = None
    if shard is not None:
        shard_specs = (s_mesh, s_axis, jax.tree_util.tree_map(
            lambda t: stacked_spec(t, s_mesh, s_axis), (g_pos, g_neg, k, scal)
        ))
    run_chunk, _ = _run_chunk_for(prog, noise_key is not None, shard_specs)

    n_chunks = (n + chunk - 1) // chunk
    keys = (
        jax.random.split(noise_key, n_chunks)
        if noise_key is not None
        else [None] * n_chunks
    )
    preds, powers, residuals = [], [], []
    # (L,) batch-wide sweep counts of every chunk, for the telemetry only.
    chunk_sweeps = [] if obs.enabled() else None
    solve_attrs = {"chunks": n_chunks, "n_samples": n}
    if shard is not None:
        solve_attrs["devices"] = n_shards
    with obs.trace("solve", solve_attrs):
        for ci in range(n_chunks):
            xb = x[ci * chunk : (ci + 1) * chunk]
            pred, pwr, res, swp = run_chunk(
                g_pos, g_neg, k, scal, xb, keys[ci]
            )
            preds.append(pred)                 # (C, B)
            powers.append(pwr * xb.shape[0])   # weight by chunk size
            residuals.append(res)
            if chunk_sweeps is not None:
                chunk_sweeps.append(swp)
    if obs.enabled():
        # The solve span ends once the chunks are enqueued; name the time
        # the host then waits for the chip.
        with obs.trace("device_wait"):
            jax.block_until_ready((preds, powers, residuals, chunk_sweeps))
    obs_prof.sample_memory("solve")
    pred = jnp.concatenate(preds, axis=1)                      # (C, n)
    per_layer_power = ordered_sum(jnp.stack(powers), axis=0) / n   # (C, L)
    worst_res = jnp.max(jnp.stack(residuals), axis=0)          # (C, L)
    if shard is not None:
        # Drop the pad lanes (replicas of config 0) before measurement.
        pred = pred[:c_real]
        per_layer_power = per_layer_power[:c_real]
        worst_res = worst_res[:c_real]

    if obs.enabled():
        # Solver convergence telemetry, recorded on the host from the
        # aux outputs that already leave the jit (no callbacks inside).
        h_sw = obs.histogram("solver_sweeps", buckets=obs.SWEEPS_BUCKETS)
        h_res = obs.histogram(
            "solver_residual", buckets=obs.RESIDUAL_BUCKETS
        )
        for swp in jax.device_get(chunk_sweeps):
            for value in swp:
                h_sw.observe(int(value))
        for value in jnp.ravel(worst_res):
            h_res.observe(float(value))
        obs.counter("solver_chunks_total").inc(n_chunks)

    with obs.trace("measure"):
        dig_pred = jnp.argmax(mlp_forward(params, x, activation), axis=-1)
        dig_acc = float(jnp.mean((dig_pred == y).astype(jnp.float32)))

        results = []
        latency_memo: dict = {}
        for i, cfg in enumerate(cfgs):
            errors = int(jnp.sum((pred[i] != y).astype(jnp.int32)))
            # The analytic latency is input-independent (structural).
            # Memoized by the fields it actually depends on — keying by
            # id(cfg) would alias distinct configs when CPython reuses the
            # address of a garbage-collected one.
            memo_key = (cfg.interconnect, cfg.resolved_neuron(), cfg.t_sampling)
            if memo_key not in latency_memo:
                latency_memo[memo_key] = float(
                    sum(
                        jnp.asarray(
                            layer_latency(p, cfg.interconnect, cfg.resolved_neuron()),
                            dtype,
                        )
                        for p in plans
                    )
                    + cfg.t_sampling
                )
            latency_an = latency_memo[memo_key]
            plp = per_layer_power[i]
            avg_power = float(jnp.sum(plp))
            if transient_res is not None:
                latency = float(transient_res.latency[i])
                energy = float(transient_res.energy[i])
                source = "transient"
                settled = bool(transient_res.settled[i])
            else:
                latency = latency_an
                energy = avg_power * latency_an
                source = "analytic"
                settled = True
            results.append(
                IMACResult(
                    accuracy=1.0 - errors / n,
                    error_rate=errors / n,
                    avg_power=avg_power,
                    latency=latency,
                    digital_accuracy=dig_acc,
                    per_layer_power=tuple(float(p) for p in plp),
                    worst_residual=float(jnp.max(worst_res[i])),
                    n_samples=n,
                    hp=tuple(p.hp for p in plans),
                    vp=tuple(p.vp for p in plans),
                    energy=energy,
                    latency_analytic=latency_an,
                    latency_source=source,
                    settled=settled,
                )
            )
    obs_prof.sample_memory("measure")
    return results


def test_imac(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    cfg: IMACConfig,
    *,
    n_samples: Optional[int] = None,
    chunk: int = 256,
    variation_key: Optional[jax.Array] = None,
    noise_key: Optional[jax.Array] = None,
    activation: str = "sigmoid",
) -> IMACResult:
    """Evaluate the IMAC deployment of `params` on (x, y).

    Thin wrapper over `evaluate_batch` with a single-configuration batch.

    Args:
      params: trained digital weights/biases [(W, b), ...].
      x: (N, fan_in) inputs in [0, 1] digital units.
      y: (N,) integer labels.
      cfg: IMAC hyperparameters (Table I).
      n_samples: N_S — number of test samples (default: all).
      chunk: samples per jitted circuit solve.
      variation_key: optional device-variation Monte-Carlo draw.
      noise_key: optional read-noise draw.

    Returns:
      IMACResult with accuracy/power/latency (Algorithm 1 lines 21-22).
    """
    return evaluate_batch(
        params,
        x,
        y,
        [cfg],
        n_samples=n_samples,
        chunk=chunk,
        variation_key=variation_key,
        noise_key=noise_key,
        activation=activation,
    )[0]


def evaluate_netlist(
    netlist,
    x: jax.Array,
    y: jax.Array,
    *,
    main: "str | None" = None,
    cfg_overrides: "Optional[dict]" = None,
    **kw,
):
    """Evaluate a SPICE netlist on (x, y) — the netlist *is* the model.

    `netlist` is either the ``{filename: contents}`` dict `map_imac`
    returns (or any equivalent multi-file deck) or an already-parsed
    `repro.spice.Circuit`. The netlist is lowered back to engine
    structures (`repro.spice.lower_network`): conductances, partition
    plans, neuron models, electrical parameters and — when the deck
    states a ``.TRAN`` — a `TransientSpec`, then evaluated through the
    same `evaluate_batch` path as a trained-parameter deployment.

    Args:
      netlist: {filename: contents} dict or a parsed Circuit.
      x, y: test inputs (digital units) and integer labels.
      main: top file name for multi-file dicts (default `imac_main.sp`).
      cfg_overrides: IMACConfig field overrides applied after lowering
        (e.g. ``{"gs_iters": 96}`` — engine tuning the netlist cannot
        state).
      **kw: forwarded to `evaluate_batch` (chunk, noise_key,
        solve_options, ...).

    Returns:
      (IMACResult, LoweredNetwork) — the result plus the lowered
      structures for inspection (recovered sample, conductances, spec).

    Raises:
      repro.spice.NonCrossbarError: the netlist is not a generated-form
        IMAC network. Flat third-party crossbars go through
        `repro.spice.lower_crossbar` + `solve_crossbar` instead.
    """
    from repro.spice.lower import lower_network

    with obs.trace("evaluate_netlist"):
        net = lower_network(netlist, main=main)
        params = [
            (jnp.asarray(w), jnp.asarray(b)) for w, b in net.to_params()
        ]
        cfg = net.to_config(**(cfg_overrides or {}))
        result = evaluate_batch(
            params, x, y, [cfg], mapped=[net.to_mapped()], **kw
        )[0]
    return result, net


def sweep(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    cfgs: "Sequence[tuple[str, IMACConfig]]",
    **kw,
) -> "list[tuple[str, IMACResult]]":
    """Design-space sweep, one configuration at a time (the paper's
    Tables III/IV are sweeps over partitioning / device technology).

    This is the reference per-config loop: every configuration runs its
    own solve (configurations of one structure share a cached jitted
    program). Prefer repro.explore.run_sweep, which
    groups structurally-compatible configurations into single vmapped
    solves and memoizes results on disk.
    """
    return [(name, test_imac(params, x, y, cfg, **kw)) for name, cfg in cfgs]


# ---------------------------------------------------------------------------
# A mixture-of-experts FFN layer: one chip's share of the experts.
# ---------------------------------------------------------------------------

#: Cells (slots x tiles x rows x cols) one chunk of an expert matrix's solve
#: may hold: 2**26 cells keep a chunk's solve temporaries near 4 GB.
MOE_CHUNK_CELLS = 1 << 26


class MappedExpert(NamedTuple):
    """One expert's tiles: gate and up side by side, then down."""

    g_up: jax.Array     # (2T, M, N) G+ tiles then G- tiles of [W_gate | W_up]
    k_up: jax.Array     # (2 * d_expert,) sense scale of each column
    g_down: jax.Array   # (2T', M, N) tiles of W_down
    k_down: float


@dataclasses.dataclass
class MoELayer:
    """This chip's share of an MoE FFN layer, mapped onto crossbar tiles.

    Made once by `map_moe`; every `evaluate_moe` call reuses its tiles.
    """

    spec: MoESpec
    cfg: IMACConfig
    held: "tuple[int, ...]"            # routed experts held here
    router_w: jax.Array                # (n_experts, d_model)
    router_bias: jax.Array             # (n_experts,)
    experts: "dict[int, MappedExpert]"  # SHARED and the held routed ids
    plan_up: PartitionPlan
    plan_down: PartitionPlan
    scalars: dict                      # the electrical values the programs read
    amp_power: float                   # W of the sense amplifiers of one pair


def map_moe(
    spec: MoESpec,
    cfg: IMACConfig,
    router_w: jax.Array,
    router_bias: jax.Array,
    experts: "dict[int, tuple[jax.Array, jax.Array, jax.Array]]",
) -> MoELayer:
    """Map this chip's experts onto `cfg`'s tiles.

    Args:
      spec: the layer's shape and router.
      cfg: technology, tile size, electrical and solver settings (the
        neuron model gives the sense amplifiers' power; no neuron is
        used).
      router_w, router_bias: the full router (every routed expert).
      experts: expert id (`SHARED`, or a routed id held here) ->
        (W_gate (d_model, d_expert), W_up (d_model, d_expert),
        W_down (d_expert, d_model)), in x @ W orientation, no biases.
        Each matrix is mapped alone (`map_matrix`).
    """
    if spec.n_shared not in (0, 1):
        raise ValueError(f"{spec.n_shared} shared experts: only 0 or 1 supported")
    if (SHARED in experts) != (spec.n_shared == 1):
        raise ValueError("the shared expert's weights and spec.n_shared disagree")
    held = tuple(sorted(e for e in experts if e != SHARED))
    if any(not 0 <= e < spec.n_experts for e in held):
        raise ValueError(f"held experts {held} outside 0..{spec.n_experts - 1}")
    tech = cfg.resolved_tech()
    dtype = cfg.dtype
    rows, cols = cfg.array_rows, cfg.array_cols
    plan_up = matrix_plan(spec.d_model, 2 * spec.d_expert, rows, cols)
    plan_down = matrix_plan(spec.d_expert, spec.d_model, rows, cols)

    def tiles(g_pos, g_neg, plan):
        return jnp.concatenate(
            [tile_matrix(g_pos, plan), tile_matrix(g_neg, plan)]
        ).astype(dtype)

    mapped = {}
    with obs.trace("map", {"experts": len(experts)}):
        for e, (w_gate, w_up, w_down) in experts.items():
            gp_g, gn_g, k_g = map_matrix(w_gate, tech, quantize=cfg.quantize)
            gp_u, gn_u, k_u = map_matrix(w_up, tech, quantize=cfg.quantize)
            g_up = tiles(jnp.concatenate([gp_g, gp_u], axis=1),
                         jnp.concatenate([gn_g, gn_u], axis=1), plan_up)
            del gp_g, gn_g, gp_u, gn_u
            gp_d, gn_d, k_d = map_matrix(w_down, tech, quantize=cfg.quantize)
            mapped[e] = MappedExpert(
                g_up=g_up,
                k_up=jnp.concatenate([jnp.full(spec.d_expert, k_g, dtype),
                                      jnp.full(spec.d_expert, k_u, dtype)]),
                g_down=tiles(gp_d, gn_d, plan_down),
                k_down=k_d,
            )
    scalars = {
        "r_seg": jnp.asarray(cfg.interconnect.r_segment, dtype),
        "r_source": jnp.asarray(cfg.r_source, dtype),
        "r_tia": jnp.asarray(cfg.r_tia, dtype),
        "omega": jnp.asarray(cfg.sor_omega, dtype),
    }
    # One TIA and amplifier per tile column of each array of the pair.
    n_amps = 2 * (plan_up.n_tiles * plan_up.cols + plan_down.n_tiles * plan_down.cols)
    return MoELayer(
        spec=spec, cfg=cfg, held=held,
        router_w=jnp.asarray(router_w, jnp.float32),
        router_bias=jnp.asarray(router_bias, jnp.float32),
        experts=mapped, plan_up=plan_up, plan_down=plan_down, scalars=scalars,
        amp_power=n_amps * cfg.resolved_neuron().p_amp,
    )


class PairDetail(NamedTuple):
    """The circuit's view of one (token, expert) pair (host arrays)."""

    z_up: np.ndarray         # (2 * d_expert,) sensed gate, then up, outputs
    h: np.ndarray            # (d_expert,) silu(gate) * up: the down input
    z_down: np.ndarray       # (d_model,) sensed down outputs: the expert's output
    tile_power_up: np.ndarray    # (2T,) W per tile, G+ tiles then G- tiles
    tile_power_down: np.ndarray  # (2T',)


class MoEPair(NamedTuple):
    """One (token, expert) pair computed on this chip."""

    token: int
    expert: int              # SHARED, or a routed expert id
    weight: float            # routing weight (1.0 for the shared expert)
    power: float             # W: its tiles' device power plus sense amplifiers
    device_power: float      # W: its tiles alone
    detail: PairDetail


class MoEResult(NamedTuple):
    """This chip's part of an MoE FFN layer for a batch of tokens."""

    out: np.ndarray          # (B, d_model) shared + weighted held experts
    out_ideal: np.ndarray    # the same with ideal crossbars (no wires)
    out_rel_err: np.ndarray  # (B,) |out - out_ideal| / |out_ideal|
    power: np.ndarray        # (B,) W of every pair of the token
    topk_idx: np.ndarray     # (B, top_k) the router's selection (all chips)
    topk_weight: np.ndarray  # (B, top_k)
    pairs: "tuple[MoEPair, ...]"  # shared first, then held experts in order


@dataclasses.dataclass(frozen=True)
class _RouteProgram:
    """The router program's static inputs (a program-cache key)."""

    spec: MoESpec


def route_all(prog: _RouteProgram, x, w_router, bias):
    return route(x, w_router, bias, prog.spec)


@dataclasses.dataclass(frozen=True)
class _ExpertProgram:
    """Static inputs of one expert matrix's program (a program-cache key):
    stage "up" runs x -> (gate, up) -> silu(gate) * up, stage "down" runs
    h -> down; each for `slots` tokens, circuit and ideal crossbar."""

    stage: str
    plan: PartitionPlan
    slots: int
    chunks: int
    iters: int
    tol: float
    v_unit: float
    dtype: jnp.dtype
    solve_options: SolveOptions

    def forward(self, g, k, sc, x, x_ideal):
        """(z, z_ideal, tile_power, sweeps): the circuit on `x`, the ideal
        crossbar on `x_ideal`."""
        cp = CircuitParams(
            r_row=sc["r_seg"], r_col=sc["r_seg"], r_source=sc["r_source"],
            r_tia=sc["r_tia"], gs_iters=self.iters, omega=sc["omega"],
            tol=self.tol,
        )
        kw = dict(v_unit=self.v_unit, chunks=self.chunks, dtype=self.dtype)
        z, power, sweeps = matrix_forward(
            g, k, self.plan, cp, x, solve_options=self.solve_options, **kw
        )
        z_ideal, _, _ = matrix_forward(
            g, k, self.plan, cp, x_ideal, parasitics=False, **kw
        )
        return z, z_ideal, power, sweeps


def expert_up(prog: _ExpertProgram, g, k, sc, x, slots):
    """Gate and up of the tokens in `slots`, and the down inputs."""
    x = jnp.take(x, slots, axis=0)
    z, z_ideal, power, sweeps = prog.forward(g, k, sc, x, x)
    f = z.shape[-1] // 2
    h = jax.nn.silu(z[:, :f]) * z[:, f:]
    h_ideal = jax.nn.silu(z_ideal[:, :f]) * z_ideal[:, f:]
    return z, h, h_ideal, power, sweeps


def expert_down(prog: _ExpertProgram, g, k, sc, h, h_ideal):
    """Down of the circuit's and the ideal crossbar's down inputs."""
    return prog.forward(g, k, sc, h, h_ideal)


def _chunks(plan: PartitionPlan, slots: int) -> int:
    """Fewest equal chunks of a matrix's 2T tiles within MOE_CHUNK_CELLS."""
    n_sys = 2 * plan.n_tiles
    cells = slots * plan.rows * plan.cols
    for c in range(1, n_sys + 1):
        if n_sys % c == 0 and cells * (n_sys // c) <= MOE_CHUNK_CELLS:
            return c
    return n_sys


def evaluate_moe(
    layer: MoELayer,
    x: jax.Array,
    *,
    solve_options: Optional[SolveOptions] = None,
) -> MoEResult:
    """This chip's part of the MoE FFN layer for a batch of tokens.

    The router runs over every routed expert. The shared expert runs on
    every token; each held expert on the tokens that selected it, padded
    to `bucket(n)` slots (pad slots repeat a real token and are dropped).
    Each expert is two programs, cached per (stage, matrix, slots) like
    `evaluate_batch`'s group programs: gate and up through the circuit
    solve, silu(gate) * up digitally, then down through the solve. The
    same programs compute the ideal crossbar (no wires) of the same
    conductances, which `out_rel_err` compares against. Unselected
    experts' tiles are not solved and draw no power.

    Args:
      layer: this chip's mapped share (`map_moe`).
      x: (B, d_model) hidden states.
      solve_options: circuit-solver backend selection; None = the process
        default.
    """
    x = jnp.asarray(x, jnp.float32)
    with obs.trace("evaluate_moe", {"tokens": int(x.shape[0])}):
        return _evaluate_moe(layer, x, _resolved_options(solve_options))


def _evaluate_moe(layer: MoELayer, x, solve_options) -> MoEResult:
    spec, cfg = layer.spec, layer.cfg
    n_tok = x.shape[0]
    with obs.trace("route"):
        run_route, _ = _cached_program(
            _RouteProgram(spec),
            lambda: jax.jit(_bind(route_all, _RouteProgram(spec))),
        )
        idx, weight = jax.device_get(
            run_route(x, layer.router_w, layer.router_bias)
        )
        work = []
        if SHARED in layer.experts:
            work.append((SHARED, np.arange(n_tok), np.ones(n_tok, np.float32)))
        work += dispatch(idx, weight, layer.held)
        slots = []
        for e, tokens, _ in work:
            n = bucket(len(tokens))
            slots.append(np.concatenate(
                [tokens, np.full(n - len(tokens), tokens[0])]).astype(np.int32))
            kind = "shared" if e == SHARED else "routed"
            obs.counter("moe_expert_tokens_total", {"kind": kind}).inc(len(tokens))
            obs.counter("moe_expert_slots_total", {"kind": "real"}).inc(len(tokens))
            obs.counter("moe_expert_slots_total", {"kind": "pad"}).inc(n - len(tokens))

    with obs.trace("prepare", {"experts": len(work)}):
        iters = cfg.gs_iters or suggest_iters(cfg.array_rows, cfg.array_cols)
        programs = []
        for s in slots:
            stages = []
            for stage, fn, plan in (("up", expert_up, layer.plan_up),
                                    ("down", expert_down, layer.plan_down)):
                prog = _ExpertProgram(
                    stage=stage, plan=plan, slots=len(s), chunks=_chunks(plan, len(s)),
                    iters=iters, tol=cfg.gs_tol, v_unit=cfg.vdd,
                    dtype=jnp.dtype(cfg.dtype), solve_options=solve_options,
                )
                stages.append(_cached_program(
                    prog, lambda fn=fn, prog=prog: jax.jit(_bind(fn, prog)))[0])
            programs.append(stages)

    sc = layer.scalars
    outs = []
    with obs.trace("solve", {"experts": len(work), "tokens": n_tok}):
        for (e, _, _), s, (run_up, run_down) in zip(work, slots, programs):
            ex = layer.experts[e]
            z_up, h, h_ideal, p_up, sw_up = run_up(ex.g_up, ex.k_up, sc, x, s)
            y, y_ideal, p_down, sw_down = run_down(ex.g_down, ex.k_down, sc, h, h_ideal)
            outs.append((z_up, h, y, y_ideal, p_up, p_down, sw_up, sw_down))
    if obs.enabled():
        with obs.trace("device_wait"):
            jax.block_until_ready(outs)
    obs_prof.sample_memory("solve")

    with obs.trace("measure"):
        outs = jax.device_get(outs)
        if obs.enabled():
            h_sw = obs.histogram("solver_sweeps", buckets=obs.SWEEPS_BUCKETS)
            for o in outs:
                for value in np.concatenate([o[6], o[7]]):
                    h_sw.observe(int(value))
        d = spec.d_model
        out = np.zeros((n_tok, d), np.float32)
        out_ideal = np.zeros((n_tok, d), np.float32)
        power = np.zeros(n_tok)
        pairs = []
        for (e, tokens, w), (z_up, h, y, y_ideal, p_up, p_down, _, _) in zip(work, outs):
            n = len(tokens)
            out[tokens] += w[:, None] * y[:n]
            out_ideal[tokens] += w[:, None] * y_ideal[:n]
            dev = (np.sum(p_up[:n], axis=1, dtype=np.float64)
                   + np.sum(p_down[:n], axis=1, dtype=np.float64))
            for i, t in enumerate(tokens):
                power[t] += dev[i] + layer.amp_power
                pairs.append(MoEPair(
                    token=int(t), expert=e, weight=float(w[i]),
                    power=float(dev[i] + layer.amp_power),
                    device_power=float(dev[i]),
                    detail=PairDetail(z_up[i], h[i], y[i], p_up[i], p_down[i]),
                ))
        norm = np.linalg.norm(out_ideal, axis=1)
        out_rel_err = np.linalg.norm(out - out_ideal, axis=1) / np.where(norm > 0, norm, 1.0)
    obs_prof.sample_memory("measure")
    return MoEResult(
        out=out, out_ideal=out_ideal, out_rel_err=out_rel_err, power=power,
        topk_idx=np.asarray(idx), topk_weight=np.asarray(weight),
        pairs=tuple(pairs),
    )
