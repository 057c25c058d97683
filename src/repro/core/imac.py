"""Composable IMAC circuit modules: IMACLinear / IMACNetwork.

This is the JAX-native equivalent of IMAC-Sim's mapLayer/mapIMAC: each DNN
layer becomes a set of partitioned crossbar tiles (differential G+/G-
arrays), solved with the batched circuit solver, followed by the
behavioural differential-amp + neuron. Power and latency are extracted
from the solved node voltages per Algorithm 1.

Fast paths:
  * parasitics=True  — full circuit solve (the paper's simulation).
  * parasitics=False — ideal analog MVM (quantisation/variation/noise only),
    optionally through the Pallas `imac_mvm` kernel.
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, NamedTuple, Optional, Sequence

if TYPE_CHECKING:  # pragma: no cover - annotation only, avoids a cycle
    from repro.transient.spec import TransientSpec
    from repro.variability.spec import VariabilitySpec

import jax
import jax.numpy as jnp

from repro.core.devices import DeviceTech, get_tech
from repro.core.interconnect import DEFAULT_INTERCONNECT, Interconnect
from repro.core.mapping import MappedLayer, map_network
from repro.core.neurons import NeuronModel, get_neuron
from repro.core.partition import (
    PartitionPlan,
    auto_partition,
    combine_outputs,
    ordered_sum,
    plan_partition,
    tile_inputs,
    tile_matrix,
)
from repro.core.solver import (
    CircuitParams,
    SolveOptions,
    _align as _align_leading,
    crossbar_power,
    solve_crossbar,
    suggest_iters,
)


@dataclasses.dataclass(frozen=True)
class IMACConfig:
    """User-facing hyperparameters (paper Table I)."""

    tech: DeviceTech | str = "MRAM"
    neuron: NeuronModel | str = "sigmoid"
    interconnect: Interconnect = DEFAULT_INTERCONNECT
    array_rows: int = 32
    array_cols: int = 32
    hp: Optional[Sequence[int]] = None   # horizontal partitions per layer
    vp: Optional[Sequence[int]] = None   # vertical partitions per layer
    vdd: float = 0.8
    vss: float = -0.8
    r_source: float = 100.0
    r_tia: float = 10.0
    parasitics: bool = True
    quantize: bool = True
    gs_iters: Optional[int] = None       # None = suggest from tile size
    sor_omega: float = 1.8
    gs_tol: float = 1e-6                 # early-exit sweep tolerance (V)
    t_sampling: float = 20e-9            # Table II: 20ns (printed as 20nm)
    dtype: jnp.dtype = jnp.float32
    # Optional Monte-Carlo reliability analysis attached to this design
    # point (repro.variability.VariabilitySpec). None = deterministic
    # point evaluation. Does not affect the traced circuit structure —
    # trials stack along the same leading config axis the design-space
    # engine already batches over.
    variability: "Optional[VariabilitySpec]" = None
    # Optional waveform-accurate transient co-simulation attached to this
    # design point (repro.transient.TransientSpec). When set, latency is
    # measured from the integrated .tran waveforms (settling detection)
    # and an integrated energy joins the result, instead of the
    # input-independent analytic Elmore estimate. The spec's static
    # fields shape the traced scan, so it participates in structure_key
    # grouping.
    transient: "Optional[TransientSpec]" = None

    def resolved_tech(self) -> DeviceTech:
        return get_tech(self.tech)

    def resolved_neuron(self) -> NeuronModel:
        n = get_neuron(self.neuron)
        if n.vdd != self.vdd or n.vss != self.vss:
            n = dataclasses.replace(n, vdd=self.vdd, vss=self.vss)
        return n

    def circuit_params(self, rows: int, cols: int) -> CircuitParams:
        iters = self.gs_iters or suggest_iters(rows, cols)
        return CircuitParams(
            r_row=self.interconnect.r_segment,
            r_col=self.interconnect.r_segment,
            r_source=self.r_source,
            r_tia=self.r_tia,
            gs_iters=iters,
            omega=self.sor_omega,
            tol=self.gs_tol,
        )


class LayerStats(NamedTuple):
    power: jax.Array       # (batch,) total layer power (W)
    latency: jax.Array     # scalar, settling latency estimate (s)
    residual: jax.Array    # worst GS residual across tiles
    z: jax.Array           # (batch, fan_out) recovered pre-activations
    sweeps: jax.Array = 0  # GS sweeps the layer's solve actually ran


class TransientStats(NamedTuple):
    """Waveform-derived statistics of one layer's transient integration.

    All arrays carry the leading (C,) stacked-configuration axis of the
    batched integration (repro.transient.engine).
    """

    t_settle: jax.Array    # (C,) measured settling time of the layer (s)
    energy: jax.Array      # (C,) integrated dissipation over the horizon (J)
    settled: jax.Array     # (C,) bool: output nodes in band at the horizon
    dt: jax.Array          # final-pass step size (s) — the time resolution
    waveform: Optional[jax.Array] = None  # (C, P, 2T, steps, N) foot voltages


class IMACLayerOutput(NamedTuple):
    activations: jax.Array
    stats: LayerStats


def build_plans(
    topology: Sequence[int], cfg: IMACConfig
) -> "list[PartitionPlan]":
    n_layers = len(topology) - 1
    hp, vp = cfg.hp, cfg.vp
    if hp is None or vp is None:
        pairs = [
            auto_partition(topology[i], topology[i + 1], cfg.array_rows, cfg.array_cols)
            for i in range(n_layers)
        ]
        hp = [p[0] for p in pairs]
        vp = [p[1] for p in pairs]
    return [
        plan_partition(topology[i], topology[i + 1], hp[i], vp[i])
        for i in range(n_layers)
    ]


def linear_forward(
    g_pos: jax.Array,
    g_neg: jax.Array,
    k: "jax.Array | float",
    v_unit: "jax.Array | float",
    plan: PartitionPlan,
    cp: CircuitParams,
    neuron,
    a: jax.Array,
    *,
    parasitics: bool = True,
    is_output: bool = False,
    solve_options: Optional[SolveOptions] = None,
    noise_key: Optional[jax.Array] = None,
    read_noise_rel: "jax.Array | float" = 0.0,
    noise_per_config: bool = False,
    dtype: jnp.dtype = jnp.float32,
) -> "tuple[jax.Array, jax.Array, jax.Array, jax.Array]":
    """Functional core of one analog layer: crossbar solve + diff amp + neuron.

    Supports stacked-configuration batches: the conductance matrices may
    carry leading config axes — g_pos/g_neg (C, fan_in+1, fan_out) with
    `k`, the electrical fields of `cp` (not `gs_iters`/`tol`) and
    `read_noise_rel` as (C,) arrays — and the whole configuration batch
    then shares ONE circuit solve / ONE compilation
    (see core/evaluate.evaluate_batch). With 2-D conductances and float
    scalars this is exactly the single-configuration layer.

    `noise_per_config` controls how read noise is drawn for stacked
    batches: False (default) shares one draw across the leading config
    axes — a paired design-space comparison; True draws independently
    per stacked entry — what a Monte-Carlo trial axis wants.

    Returns:
      (activations, power, residual, z, sweeps) — power is (..., batch),
      residual (...,), z the recovered pre-activations, sweeps the GS
      trip count of the layer's circuit solve (0 without parasitics).
    """
    # Bias input: driven at v_unit (logical activation 1).
    ones = jnp.ones(a.shape[:-1] + (1,), dtype)
    v = jnp.concatenate([a.astype(dtype), ones], axis=-1) * v_unit

    sweeps = jnp.zeros((), jnp.int32)
    if not parasitics:
        g_diff = (g_pos - g_neg).astype(dtype)
        # HIGHEST: on TPU an f32 dot defaults to bf16 passes, which
        # would put bf16 rounding into simulated currents and power.
        highest = jax.lax.Precision.HIGHEST
        i_diff = jnp.einsum(
            "...mn,...bm->...bn", g_diff, v, precision=highest
        )
        p_dev = jnp.einsum(
            "...mn,...bm->...b", g_pos + g_neg, v**2, precision=highest
        )
        residual = jnp.zeros(g_pos.shape[:-2], dtype)
    else:
        tiles_p = tile_matrix(g_pos.astype(dtype), plan)
        tiles_n = tile_matrix(g_neg.astype(dtype), plan)
        g_all = jnp.concatenate([tiles_p, tiles_n], axis=-3)  # (..., 2T, M, N)
        v_tiles = tile_inputs(v, plan)                        # (..., batch, hp, M)
        # tile t = h*vp + vcol shares the h-th input slice.
        v_per_tile = jnp.repeat(v_tiles, plan.vp, axis=-2)    # (..., batch, T, M)
        v_all = jnp.concatenate([v_per_tile, v_per_tile], axis=-2)  # (..., b, 2T, M)
        # Insert the sample axis into g: (..., 1, 2T, M, N) vs (..., b, 2T, M).
        g_b = g_all[..., None, :, :, :]
        sol = solve_crossbar(g_b, v_all, cp, options=solve_options)
        t = plan.n_tiles
        i_pos = combine_outputs(sol.i_out[..., :t, :], plan)
        i_neg = combine_outputs(sol.i_out[..., t:, :], plan)
        i_diff = i_pos - i_neg
        p_dev = ordered_sum(crossbar_power(g_b, v_all, sol, cp))
        residual = jnp.max(sol.residual, axis=(-1, -2))
        sweeps = jnp.asarray(sol.sweeps, jnp.int32)

    if noise_key is not None:
        # Default: one draw shared by every stacked configuration —
        # identical to evaluating each configuration separately with the
        # same key. Per-config: independent noise along the leading axes.
        shape = i_diff.shape if noise_per_config else i_diff.shape[-2:]
        noise = jax.random.normal(noise_key, shape, dtype)
        rel = _align_leading(read_noise_rel, i_diff.ndim, dtype)
        scale = rel * jnp.maximum(jnp.abs(i_diff), 1e-12)
        i_diff = i_diff + scale * noise

    # Differential sense: recover digital pre-activation.
    z = i_diff / (_align_leading(k, i_diff.ndim, dtype) * v_unit)
    z = neuron.clip_preactivation(z)
    act = z if is_output else neuron.activation(z)

    # Interface power: one TIA+amp per tile column, one neuron per output.
    n_amps = plan.hp * plan.vp * plan.cols * 2  # differential pair sensing
    n_neurons = plan.total_cols
    p_iface = n_amps * neuron.p_amp + n_neurons * neuron.p_neuron
    power = p_dev + p_iface
    return act, power, residual, z, sweeps


def matrix_plan(fan_in: int, fan_out: int, rows: int, cols: int) -> PartitionPlan:
    """Tiling of a bias-free (fan_in, fan_out) matrix on rows x cols arrays:
    the fewest partitions that fit, with no bias row."""
    hp = math.ceil(fan_in / rows)
    vp = math.ceil(fan_out / cols)
    return PartitionPlan(
        hp=hp, vp=vp, rows=math.ceil(fan_in / hp), cols=math.ceil(fan_out / vp),
        total_rows=fan_in, total_cols=fan_out,
    )


def matrix_forward(
    g_tiles: jax.Array,
    k: "jax.Array | float",
    plan: PartitionPlan,
    cp: CircuitParams,
    x: jax.Array,
    *,
    v_unit: float,
    chunks: int = 1,
    parasitics: bool = True,
    solve_options: Optional[SolveOptions] = None,
    dtype: jnp.dtype = jnp.float32,
) -> "tuple[jax.Array, jax.Array, jax.Array]":
    """Analog MVM of a weight matrix with no bias, no neuron and signed inputs.

    Each token's inputs are scaled digitally so that its largest magnitude
    drives `v_unit` volts (negative inputs drive negative volts); the scale
    is undone after sensing, so z approximates x @ W.

    Args:
      g_tiles: (2T, M, N) conductances: the T tiles of G+ (`tile_matrix`
        order, tile t = h * vp + v), then the T tiles of G-.
      k: sense scale (S per unit weight), a scalar or one per output column.
      x: (B, fan_in) inputs in digital units.
      chunks: the tiles are solved in this many equal chunks, one after
        another, which bounds the solve's temporaries.
      parasitics: False = ideal crossbar (I = G^T V) on the same tiles.

    Returns:
      (z, tile_power, sweeps): z (B, fan_out) sensed outputs; tile_power
      (B, 2T) device power of each tile (W); sweeps (chunks,) the
      Gauss-Seidel sweeps of each chunk's solve (0 for the ideal crossbar).
    """
    x = x.astype(dtype)
    peak = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(peak > 0, peak, jnp.ones_like(peak))
    v = x * (v_unit / scale)
    v_tiles = tile_inputs(v, plan)                          # (B, hp, M)
    v_per_tile = jnp.repeat(v_tiles, plan.vp, axis=-2)      # (B, T, M)
    v_all = jnp.concatenate([v_per_tile, v_per_tile], axis=-2)  # (B, 2T, M)
    g_tiles = g_tiles.astype(dtype)
    n_sys, m, n = g_tiles.shape
    b = x.shape[0]
    if parasitics:
        g_c = g_tiles.reshape(chunks, n_sys // chunks, m, n)
        v_c = jnp.moveaxis(v_all.reshape(b, chunks, n_sys // chunks, m), 1, 0)

        def solve_chunk(args):
            g, vv = args
            g_b = g[None]                                   # (1, c, M, N)
            sol = solve_crossbar(g_b, vv, cp, options=solve_options)
            power = crossbar_power(g_b, vv, sol, cp)        # (B, c)
            return sol.i_out, power, jnp.asarray(sol.sweeps, jnp.int32)

        i_out, tile_power, sweeps = jax.lax.map(solve_chunk, (g_c, v_c))
        i_out = jnp.moveaxis(i_out, 0, 1).reshape(b, n_sys, n)
        tile_power = jnp.moveaxis(tile_power, 0, 1).reshape(b, n_sys)
    else:
        highest = jax.lax.Precision.HIGHEST
        i_out = jnp.einsum("btm,tmn->btn", v_all, g_tiles, precision=highest)
        tile_power = jnp.einsum(
            "btm,tmn->bt", v_all**2, g_tiles, precision=highest
        )
        sweeps = jnp.zeros((chunks,), jnp.int32)
    t = plan.n_tiles
    i_diff = combine_outputs(i_out[:, :t], plan) - combine_outputs(i_out[:, t:], plan)
    z = i_diff * scale / (jnp.asarray(k, dtype) * v_unit)
    return z, tile_power, sweeps


def layer_latency(plan: PartitionPlan, interconnect: Interconnect, neuron) -> float:
    """Structural settling latency of one layer (input-independent).

    Elmore delay of the row+column lines (1% settling ~ 4.6 tau) + neuron.
    """
    t_line = 4.6 * (
        interconnect.elmore_delay(plan.cols) + interconnect.elmore_delay(plan.rows)
    )
    return t_line + neuron.t_settle


def imac_linear(
    mapped: MappedLayer,
    plan: PartitionPlan,
    a: jax.Array,
    cfg: IMACConfig,
    *,
    is_output: bool = False,
    solve_options: Optional[SolveOptions] = None,
    noise_key: Optional[jax.Array] = None,
) -> IMACLayerOutput:
    """One analog layer: crossbar solve + diff amp + neuron.

    Args:
      mapped: differential conductances (bias row folded).
      plan: tiling over (hp, vp) partitions.
      a: (batch, fan_in) activations in digital units.
      cfg: circuit hyperparameters.
      is_output: last layer — linear readout (no neuron nonlinearity).
      solve_options: solver backend selection (None = process default).
      noise_key: optional key for read noise on the output currents.

    Returns:
      activations (batch, fan_out) and per-layer circuit stats.
    """
    tech = cfg.resolved_tech()
    neuron = cfg.resolved_neuron()
    dtype = cfg.dtype
    if not (noise_key is not None and tech.read_noise_rel > 0.0):
        noise_key = None
    act, power, residual, z, sweeps = linear_forward(
        mapped.g_pos,
        mapped.g_neg,
        mapped.k,
        mapped.v_unit,
        plan,
        cfg.circuit_params(plan.rows, plan.cols),
        neuron,
        a,
        parasitics=cfg.parasitics,
        is_output=is_output,
        solve_options=solve_options,
        noise_key=noise_key,
        read_noise_rel=tech.read_noise_rel,
        dtype=dtype,
    )
    latency = jnp.asarray(layer_latency(plan, cfg.interconnect, neuron), dtype)
    return IMACLayerOutput(
        activations=act,
        stats=LayerStats(
            power=power, latency=latency, residual=residual, z=z, sweeps=sweeps
        ),
    )


class IMACNetwork:
    """The full mapped network (mapIMAC): layers + plans + forward.

    Construction performs mapWB (Module 2) and partition planning
    (Module 3's tiling); `__call__` simulates the concatenated circuit
    (Module 4 + Algorithm 1's SPICE run), returning outputs and stats.
    """

    def __init__(
        self,
        params: "list[tuple[jax.Array, jax.Array]]",
        cfg: IMACConfig,
        *,
        variation_key: Optional[jax.Array] = None,
    ):
        self.cfg = cfg
        tech = cfg.resolved_tech()
        topology = [params[0][0].shape[0]] + [w.shape[1] for w, _ in params]
        self.topology = topology
        self.mapped = map_network(
            params,
            tech,
            v_unit=cfg.vdd,
            quantize=cfg.quantize,
            variation_key=variation_key,
        )
        self.plans = build_plans(topology, cfg)

    @property
    def hp(self) -> "list[int]":
        return [p.hp for p in self.plans]

    @property
    def vp(self) -> "list[int]":
        return [p.vp for p in self.plans]

    def __call__(
        self,
        x: jax.Array,
        *,
        solve_options: Optional[SolveOptions] = None,
        noise_key: Optional[jax.Array] = None,
    ) -> "tuple[jax.Array, list[LayerStats]]":
        """Simulate the full IMAC circuit for a batch of inputs.

        Args:
          x: (batch, n_inputs) in digital activation units ([0,1] for
            sigmoid networks).

        Returns:
          (batch, n_outputs) final pre-activations (linear readout) and
          per-layer stats.
        """
        a = x
        stats: list[LayerStats] = []
        n = len(self.mapped)
        keys = (
            jax.random.split(noise_key, n) if noise_key is not None else [None] * n
        )
        for idx, (mapped, plan) in enumerate(zip(self.mapped, self.plans)):
            out = imac_linear(
                mapped,
                plan,
                a,
                self.cfg,
                is_output=(idx == n - 1),
                solve_options=solve_options,
                noise_key=keys[idx],
            )
            a = out.activations
            stats.append(out.stats)
        return a, stats

    def total_power(self, stats: "list[LayerStats]") -> jax.Array:
        """Mean-over-batch total circuit power (W)."""
        return sum(jnp.mean(s.power) for s in stats)

    def total_latency(self, stats: "list[LayerStats]") -> jax.Array:
        """End-to-end settling latency + sampling time (s)."""
        return sum(s.latency for s in stats) + self.cfg.t_sampling
