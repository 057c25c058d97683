"""Plain float64 reference of an IMAC deployment, written against the circuit.

Nothing here imports the simulator. From the configuration file and the
digital weights it rebuilds, in NumPy float64:

  * the conductance mapping: per layer, weights and bias row scaled by
    the layer's largest magnitude onto [G_off, G_on] of the technology,
    one differential pair per weight (G+ for w > 0, G- for w < 0);
  * the partitioning: H_P x V_P tiles of ceil(rows / H_P) x
    ceil(cols / V_P) cells, padding cells left without a device;
  * each tile's resistive wire grid, solved exactly by a sparse LU of its
    modified nodal analysis: row nodes r(i, j) and column nodes c(i, j),
    a wire segment between neighbours on each line, the device between
    r(i, j) and c(i, j), the input source into r(i, 0) through r_source and the
    TIA from c(M-1, j) to ground through r_tia. The matrix depends only on
    the tile, so one factorisation serves every sample;
  * the recombination: partial column currents summed over horizontal
    partitions, the differential current sensed as z = I / (k * vdd),
    clipped at the rails (|z| <= vdd / z_volt), sigmoid between layers
    and a linear readout, argmax for the prediction;
  * per-layer power: the power the input sources deliver (every resistor of the
    grid, source resistors and TIAs included), plus one amplifier per
    tile column of each differential pair and one neuron per output.

The ideal (no wire) crossbar replaces the grid by I = G^T V and its power
by sum (G+ + G-) V^2.

`forward` takes the conductances themselves, so that a Monte-Carlo trial
(`benchlib.trials`) is solved with its own drawn devices, one
factorisation per trial and tile, and optional read noise on the sensed
differential currents, I += rel * max(|I|, 1e-12) * draw.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spl


@dataclasses.dataclass(frozen=True)
class Plan:
    hp: int
    vp: int
    rows: int
    cols: int
    total_rows: int
    total_cols: int


def plan_layers(topology, partitioning) -> "list[Plan]":
    """Tile plan of every layer; H_P / V_P given or the fewest that fit."""
    plans = []
    for layer, (fan_in, fan_out) in enumerate(zip(topology[:-1], topology[1:])):
        total_rows = fan_in + 1  # bias row
        if "hp" in partitioning:
            hp, vp = partitioning["hp"][layer], partitioning["vp"][layer]
        else:
            hp = math.ceil(total_rows / partitioning["array_rows"])
            vp = math.ceil(fan_out / partitioning["array_cols"])
        plans.append(Plan(hp, vp, math.ceil(total_rows / hp),
                          math.ceil(fan_out / vp), total_rows, fan_out))
    return plans


def map_layer(w, b, tech):
    """(G+, G-, k) of one layer; k converts sensed current to weight units."""
    wb = np.concatenate([np.asarray(w, np.float64),
                         np.asarray(b, np.float64)[None, :]])
    scale = float(np.max(np.abs(wb))) or 1.0
    g_on, g_off = 1.0 / tech["r_low"], 1.0 / tech["r_high"]
    wn = wb / scale
    g_pos = np.clip(g_off + np.maximum(wn, 0.0) * (g_on - g_off), g_off, g_on)
    g_neg = np.clip(g_off + np.maximum(-wn, 0.0) * (g_on - g_off), g_off, g_on)
    return g_pos, g_neg, (g_on - g_off) / scale


def r_segment(ic) -> float:
    """Wire resistance of one bitcell pitch (ohms)."""
    return ic["resistivity_ohm_m"] * ic["pitch_m"] / (ic["width_m"] * ic["thickness_m"])


class TileSolver:
    """Factorised MNA system of one tile; solves any number of drives."""

    def __init__(self, g, r_seg, r_src, r_tia):
        m, n = g.shape
        mn = m * n
        r = np.arange(mn).reshape(m, n)
        c = r + mn
        rows, cols, vals = [], [], []

        def stamp(p, q, cond):
            p, q = p.ravel(), q.ravel()
            cond = np.broadcast_to(cond, p.shape).ravel()
            rows.extend([p, q, p, q])
            cols.extend([p, q, q, p])
            vals.extend([cond, cond, -cond, -cond])

        stamp(r[:, :-1], r[:, 1:], 1.0 / r_seg)   # row wires
        stamp(c[:-1, :], c[1:, :], 1.0 / r_seg)   # column wires
        live = g.ravel() > 0                      # padding cells hold no device
        stamp(r.ravel()[live], c.ravel()[live], g.ravel()[live])
        rows.extend([r[:, 0], c[-1]])
        cols.extend([r[:, 0], c[-1]])
        vals.extend([np.full(m, 1.0 / r_src), np.full(n, 1.0 / r_tia)])
        a = sp.csc_matrix(
            (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
            shape=(2 * mn, 2 * mn),
        )
        self.lu = spl.splu(a, permc_spec="MMD_AT_PLUS_A")
        self.m, self.n, self.r_src, self.r_tia = m, n, r_src, r_tia
        self.drive_nodes, self.tia_nodes = r[:, 0], c[-1]

    def solve(self, v):
        """v: (S, M) source voltages -> (i_out (S, N), power (S,))."""
        rhs = np.zeros((2 * self.m * self.n, v.shape[0]))
        rhs[self.drive_nodes] = v.T / self.r_src
        x = self.lu.solve(rhs)
        i_out = x[self.tia_nodes].T / self.r_tia
        i_src = (v - x[self.drive_nodes].T) / self.r_src
        return i_out, np.sum(v * i_src, axis=1)


def tiles_of(g, plan: Plan):
    """{(h, v): (rows, cols) tile}, padding cells 0 (no device)."""
    padded = np.zeros((plan.hp * plan.rows, plan.vp * plan.cols))
    padded[: plan.total_rows, : plan.total_cols] = g
    return {
        (h, v): padded[h * plan.rows:(h + 1) * plan.rows,
                       v * plan.cols:(v + 1) * plan.cols]
        for h in range(plan.hp) for v in range(plan.vp)
    }


def interface_power(plan: Plan, neuron) -> float:
    n_amps = plan.hp * plan.vp * plan.cols * 2
    return n_amps * neuron["p_amp_w"] + plan.total_cols * neuron["p_neuron_w"]


@dataclasses.dataclass
class Outcome:
    """Reference results of one design point on a batch of samples."""

    errors: int                     # misclassified inputs
    layer_power: np.ndarray         # (L,) W, mean over samples
    layer_device_power: np.ndarray  # (L,) the part that is not interface


def evaluate_point(cfg, point, params, x, y, *, parasitics=True) -> Outcome:
    """Forward `x` through the deployment `point` of configuration `cfg`."""
    tech = cfg["technologies"][point["tech"]]
    layers = [map_layer(w, b, tech) for w, b in params]
    return forward(cfg, point["partitioning"], layers, x, y, parasitics=parasitics)


def forward(cfg, partitioning, layers, x, y, *, parasitics=True,
            read_noise=None) -> Outcome:
    """Forward `x` through conductances `layers`, per layer (G+, G-, k) as
    `map_layer` gives them, tiled by `partitioning`. `read_noise`: None, or
    per layer (rel, draws (samples, fan_out))."""
    vdd = cfg["vdd"]
    neuron = cfg["neuron"]
    z_lim = vdd / neuron["z_volt"]
    r_seg = r_segment(cfg["interconnect"])
    plans = plan_layers(cfg["topology"], partitioning)
    a = np.asarray(x, np.float64)
    powers, dev_powers = [], []
    for layer, ((g_pos, g_neg, k), plan) in enumerate(zip(layers, plans)):
        v = np.concatenate([a, np.ones((a.shape[0], 1))], axis=1) * vdd
        if parasitics:
            v_pad = np.zeros((v.shape[0], plan.hp * plan.rows))
            v_pad[:, : plan.total_rows] = v
            i_diff = np.zeros((v.shape[0], plan.vp * plan.cols))
            p_dev = np.zeros(v.shape[0])
            for sign, g in ((1.0, g_pos), (-1.0, g_neg)):
                for (h, vcol), tile in tiles_of(g, plan).items():
                    solver = TileSolver(tile, r_seg, cfg["r_source_ohm"],
                                        cfg["r_tia_ohm"])
                    i_out, p = solver.solve(
                        v_pad[:, h * plan.rows:(h + 1) * plan.rows])
                    i_diff[:, vcol * plan.cols:(vcol + 1) * plan.cols] += sign * i_out
                    p_dev += p
            i_diff = i_diff[:, : plan.total_cols]
        else:
            i_diff = v @ (g_pos - g_neg)
            p_dev = (v ** 2) @ (g_pos + g_neg).sum(axis=1)
        if read_noise is not None:
            rel, draws = read_noise[layer]
            i_diff = i_diff + rel * np.maximum(np.abs(i_diff), 1e-12) * draws
        z = np.clip(i_diff / (k * vdd), -z_lim, z_lim)
        a = z if layer == len(layers) - 1 else 1.0 / (1.0 + np.exp(-z))
        dev_powers.append(float(np.mean(p_dev)))
        powers.append(dev_powers[-1] + interface_power(plan, neuron))
    return Outcome(
        errors=int(np.sum(np.argmax(a, axis=1) != np.asarray(y))),
        layer_power=np.asarray(powers),
        layer_device_power=np.asarray(dev_powers),
    )
