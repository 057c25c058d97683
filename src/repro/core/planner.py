"""Analog deployment planner: the paper's design-space exploration
applied to the assigned LM architectures.

For every weight matrix of an arch, plan the crossbar tiling (auto
H_P/V_P per Table III arithmetic), count subarrays and devices, and
estimate static+read power and per-MVM latency with the same circuit
cost model the MNIST pipeline uses. This answers the paper's question —
"what does deploying this network on IMAC cost?" — for 2024-25 LLMs
(benchmarks/deploy_report.py prints the table).

Dynamic matmuls (attention scores) and stateful mixers stay digital;
only static projection weights map to crossbars (the paper's
static-conductance assumption; DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import ModelConfig
from repro.core.devices import DeviceTech, get_tech
from repro.core.interconnect import DEFAULT_INTERCONNECT
from repro.core.neurons import get_neuron
from repro.core.partition import auto_partition


@dataclasses.dataclass(frozen=True)
class MatrixPlan:
    name: str
    fan_in: int
    fan_out: int
    count: int          # how many instances (layers x experts ...)
    hp: int
    vp: int
    activity: float = 1.0   # share of tokens that drive it (routed experts)

    @property
    def tiles_per_instance(self) -> int:
        return self.hp * self.vp

    @property
    def tiles(self) -> int:
        return self.tiles_per_instance * self.count

    @property
    def devices(self) -> int:
        # differential pair -> 2 devices per logical weight (+ bias row)
        return 2 * (self.fan_in + 1) * self.fan_out * self.count


@dataclasses.dataclass
class DeploymentReport:
    arch: str
    tech: str
    array_rows: int
    array_cols: int
    matrices: "List[MatrixPlan]"
    total_tiles: int
    total_devices: int
    est_power_w: float       # tiles at their activity, mean conductance
    est_latency_ns: float    # one analog MVM through the deepest layer
    area_mm2: float          # bitcell-pitch estimate

    def as_row(self) -> Dict[str, object]:
        return {
            "arch": self.arch,
            "tech": self.tech,
            "array": f"{self.array_rows}x{self.array_cols}",
            "tiles": self.total_tiles,
            "devices": self.total_devices,
            "est_power_w": round(self.est_power_w, 1),
            "est_latency_ns": round(self.est_latency_ns, 2),
            "area_mm2": round(self.area_mm2, 1),
        }


def _arch_matrices(cfg: ModelConfig) -> "list[tuple[str, int, int, int, float]]":
    """The static projection matrices of one arch: (name, fan_in, fan_out,
    count, activity). A routed expert's matrices are driven by
    experts_per_token / n_experts of the tokens; every other matrix by all."""
    e, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out: "list[tuple[str, int, int, int]]" = []
    n_attn = sum(cfg.is_attn_layer(i) for i in range(cfg.n_layers))
    n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    n_dense = cfg.n_layers - n_moe
    if cfg.ssm_type == "rwkv6":
        out += [("rwkv_rkvgo", e, e, 5 * cfg.n_layers)]
        out += [("cm_wk", e, f, cfg.n_layers), ("cm_wv", f, e, cfg.n_layers)]
    else:
        if cfg.attn_type == "mla":
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            out += [
                ("w_dq", e, cfg.q_lora_rank or e, n_attn),
                ("w_uq", cfg.q_lora_rank or e, h * qk, n_attn),
                ("w_dkv", e, cfg.kv_lora_rank + cfg.qk_rope_head_dim, n_attn),
                ("w_ukv", cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim), n_attn),
                ("w_o", h * cfg.v_head_dim, e, n_attn),
            ]
        else:
            out += [
                ("wq", e, h * hd, n_attn),
                ("wk", e, kv * hd, n_attn),
                ("wv", e, kv * hd, n_attn),
                ("wo", h * hd, e, n_attn),
            ]
        if cfg.ssm_type == "mamba":
            n_mamba = cfg.n_layers - n_attn
            di = cfg.ssm_expand * e
            out += [
                ("mamba_in", e, 2 * di, n_mamba),
                ("mamba_out", di, e, n_mamba),
            ]
        if n_dense:
            out += [
                ("mlp_gate", e, f, n_dense),
                ("mlp_up", e, f, n_dense),
                ("mlp_down", f, e, n_dense),
            ]
        if n_moe and cfg.n_shared_experts:
            fe, n_sh = cfg.moe_d_ff, n_moe * cfg.n_shared_experts
            out += [
                ("shared_gate", e, fe, n_sh),
                ("shared_up", e, fe, n_sh),
                ("shared_down", fe, e, n_sh),
            ]
    out += [("lm_head", e, cfg.vocab, 1)]
    matrices = [(*m, 1.0) for m in out]
    if n_moe:
        fe, n_exp = cfg.moe_d_ff, n_moe * cfg.n_experts
        activity = cfg.experts_per_token / cfg.n_experts
        matrices += [
            ("moe_gate", e, fe, n_exp, activity),
            ("moe_up", e, fe, n_exp, activity),
            ("moe_down", fe, e, n_exp, activity),
        ]
    return matrices


def plan_arch(
    cfg: ModelConfig,
    tech: "DeviceTech | str" = "PCM",
    array_rows: int = 512,
    array_cols: int = 512,
) -> DeploymentReport:
    tech = get_tech(tech)
    neuron = get_neuron("sigmoid")
    ic = DEFAULT_INTERCONNECT
    plans = []
    for name, fi, fo, count, activity in _arch_matrices(cfg):
        hp, vp = auto_partition(fi, fo, array_rows, array_cols)
        plans.append(MatrixPlan(name, fi, fo, count, hp, vp, activity))

    total_tiles = sum(p.tiles for p in plans)
    total_devices = sum(p.devices for p in plans)

    # Power: mean device conductance at half swing + interface circuits,
    # each matrix weighted by the share of tokens that drive it.
    g_mean = 0.5 * (tech.g_on + tech.g_off)
    v_mean = 0.5 * neuron.vdd
    p_device = g_mean * v_mean**2
    est_power = sum(
        p.activity * (
            p.devices * p_device
            + p.fan_out * p.count * p.hp * 2 * neuron.p_amp
            + p.fan_out * p.count * neuron.p_neuron
        )
        for p in plans
    )

    # Latency: deepest chain = layers in sequence, two analog MVMs a layer
    # (attention or mixer projections, then the FFN); per-tile Elmore.
    t_tile = 4.6 * (ic.elmore_delay(array_cols) + ic.elmore_delay(array_rows))
    depth = 2 * cfg.n_layers
    est_latency = depth * (t_tile + neuron.t_settle)

    # Area: bitcell pitch^2 per device (2 devices/weight already counted).
    area = total_devices * (ic.pitch**2) * 1e6  # m^2 -> mm^2 (1e6 mm2/m2)
    return DeploymentReport(
        arch=cfg.name,
        tech=tech.name,
        array_rows=array_rows,
        array_cols=array_cols,
        matrices=plans,
        total_tiles=total_tiles,
        total_devices=total_devices,
        est_power_w=float(est_power),
        est_latency_ns=float(est_latency * 1e9),
        area_mm2=float(area),
    )
