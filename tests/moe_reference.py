"""Plain float64 reference of one chip's share of an MoE FFN layer.

Written from the published layer (DeepSeek-V3, arXiv:2412.19437) and the
circuit, not from the program's code paths: the router in NumPy; each
matrix mapped alone onto [G_off, G_on] by its largest magnitude, no bias
row; tiles cut by NumPy slicing; every tile solved by the dense MNA oracle
(`repro.core.solver.solve_dense_mna`) in float64; device power as the
power the row sources deliver; the SwiGLU glue in NumPy.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import CircuitParams, solve_dense_mna

SHARED = -1


def route(x, w_router, bias, spec):
    """(idx (B, k), weight (B, k)) of the group-limited sigmoid router."""
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                   @ np.asarray(w_router, np.float64).T)))
    choice = scores + np.asarray(bias, np.float64)
    per_group = spec.n_experts // spec.n_group
    idx = np.zeros((len(scores), spec.top_k), np.int64)
    weight = np.zeros((len(scores), spec.top_k))
    for t, c in enumerate(choice):
        groups = c.reshape(spec.n_group, per_group)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-group_score, kind="stable")[: spec.topk_group]
        masked = np.zeros_like(c)
        for g in kept:
            masked[g * per_group:(g + 1) * per_group] = c[g * per_group:(g + 1) * per_group]
        idx[t] = np.argsort(-masked, kind="stable")[: spec.top_k]
        w = scores[t, idx[t]]
        if spec.norm_topk_prob:
            w = w / (w.sum() + 1e-20)
        weight[t] = w * spec.scaling
    return idx, weight


def map_matrix(w, tech):
    w = np.asarray(w, np.float64)
    scale = float(np.max(np.abs(w))) or 1.0
    g_pos = np.clip(tech.g_off + np.maximum(w / scale, 0) * tech.g_range, tech.g_off, tech.g_on)
    g_neg = np.clip(tech.g_off + np.maximum(-w / scale, 0) * tech.g_range, tech.g_off, tech.g_on)
    return g_pos, g_neg, tech.g_range / scale


class Crossbar:
    """One matrix on rows x cols tiles, solved tile by tile."""

    def __init__(self, w, cfg):
        self.g_pos, self.g_neg, self.k = map_matrix(w, cfg.resolved_tech())
        self.cfg = cfg
        fan_in, fan_out = self.g_pos.shape
        self.hp = math.ceil(fan_in / cfg.array_rows)
        self.vp = math.ceil(fan_out / cfg.array_cols)
        self.rows = math.ceil(fan_in / self.hp)
        self.cols = math.ceil(fan_out / self.vp)
        self.cp = CircuitParams(
            r_row=cfg.interconnect.r_segment, r_col=cfg.interconnect.r_segment,
            r_source=cfg.r_source, r_tia=cfg.r_tia,
        )

    def tiles(self, g):
        padded = np.zeros((self.hp * self.rows, self.vp * self.cols))
        padded[: g.shape[0], : g.shape[1]] = g
        return [padded[h * self.rows:(h + 1) * self.rows, v * self.cols:(v + 1) * self.cols]
                for h in range(self.hp) for v in range(self.vp)]

    def __call__(self, x, parasitics=True):
        """(z (fan_out,), device power (W)) of one signed input vector."""
        x = np.asarray(x, np.float64)
        scale = np.max(np.abs(x)) or 1.0
        vdd = self.cfg.vdd
        v = np.zeros(self.hp * self.rows)
        v[: x.size] = x * vdd / scale
        drives = [v[h * self.rows:(h + 1) * self.rows]
                  for h in range(self.hp) for _ in range(self.vp)]
        i_diff = np.zeros(self.vp * self.cols)
        power = 0.0
        for sign, g in ((1.0, self.g_pos), (-1.0, self.g_neg)):
            tiles = np.stack(self.tiles(g))
            if parasitics:
                i_out, p = solve_tiles(tiles, np.stack(drives), self.cp)
            else:
                i_out = np.einsum("tmn,tm->tn", tiles, np.stack(drives))
                p = np.einsum("tmn,tm->t", tiles, np.stack(drives) ** 2)
            for t, cur in enumerate(i_out):
                vcol = t % self.vp
                i_diff[vcol * self.cols:(vcol + 1) * self.cols] += sign * cur
            power += float(np.sum(p))
        z = i_diff[: self.g_pos.shape[1]] * scale / (self.k * vdd)
        return z, power


_SOLVERS = {}


def solve_tiles(g, v, cp):
    """Dense-MNA solve of a stack of tiles: (i_out (T, N), power (T,))."""
    key = (g.shape[1:], cp)
    with jax.enable_x64(True):
        if key not in _SOLVERS:
            def one(gt, vt):
                sol = solve_dense_mna(gt, vt, cp)
                return sol.i_out, jnp.sum(vt * (vt - sol.vr[:, 0])) * cp.g_source
            _SOLVERS[key] = jax.jit(jax.vmap(one))
        i_out, p = _SOLVERS[key](jnp.asarray(g, jnp.float64), jnp.asarray(v, jnp.float64))
        return np.asarray(i_out), np.asarray(p)


def expert(weights, cfg, x, parasitics=True):
    """(output (d_model,), device power) of one SwiGLU expert on one token."""
    w_gate, w_up, w_down = weights
    gate, p_g = Crossbar(w_gate, cfg)(x, parasitics)
    up, p_u = Crossbar(w_up, cfg)(x, parasitics)
    h = gate / (1.0 + np.exp(-gate)) * up
    y, p_d = Crossbar(w_down, cfg)(h, parasitics)
    return y, p_g + p_u + p_d


def layer(x, router_w, bias, experts, spec, cfg, parasitics=True):
    """This chip's part for tokens `x`: (out (B, d), routing, {(t, e): power})
    with `experts` the shared and held experts' weights."""
    x = np.asarray(x, np.float64)
    idx, weight = route(x, router_w, bias, spec)
    out = np.zeros_like(x)
    powers = {}
    for t in range(len(x)):
        pairs = [(SHARED, 1.0)] if SHARED in experts else []
        pairs += [(int(e), w) for e, w in zip(idx[t], weight[t]) if int(e) in experts]
        for e, w in pairs:
            y, p = expert(experts[e], cfg, x[t], parasitics)
            out[t] += w * y
            powers[(t, e)] = p
    return out, (idx, weight), powers
