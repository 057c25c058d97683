"""Plain float64 reference of one chip's share of an MoE FFN layer.

Nothing here imports the simulator. From the configuration file and the
weights it rebuilds, in NumPy float64:

  * the router (DeepSeek-V3, `topk_method` "noaux_tc"): sigmoid scores of
    x W_r^T; selection on scores plus the correction bias; each group of
    n_experts / n_group scored by the sum of its two best biased scores;
    the best `topk_group` groups kept and the others' experts set to 0;
    the `num_experts_per_tok` best experts taken; their unbiased scores
    normalised to sum 1 and times `routed_scaling_factor`. Each token's
    margin is the smaller of the two gaps that decide it (the last kept
    group against the first dropped, the last selected expert against
    the first not selected): float32 rounding can flip a decision only
    where the margin is of its order;
  * the mapping of each matrix alone, with no bias row: W scaled by its
    largest magnitude onto [G_off, G_on], one differential pair per
    weight, k = (G_on - G_off) / max|W|;
  * a column block of a matrix: the `cols` outputs of one vertical
    partition, over the `rows`-row tiles of every horizontal partition,
    G+ and G- arrays. Each tile is solved exactly (below), driven by the
    input scaled so that its largest magnitude is at vdd; the block's
    output is sum over tiles of (I+ - I-) * scale / (k * vdd) and its
    device power what its row sources deliver.

Each tile's nodal system (the one `benchlib.reference.TileSolver` builds)
is solved exactly, in float64, by block elimination instead of a general
sparse LU, which takes about three times as long at 64 x 64: each row
wire is a tridiagonal chain, so the row nodes are eliminated with the
chains' inverses; the Schur complement in the column nodes is banded
(half-bandwidth N in row-major order) and positive definite, and is
factorised by LAPACK's banded Cholesky (`dpbsv`). `bench/tests/test_moe.py` holds
it to the sparse LU on random tiles. Blocks are solved on a pool of
threads (LAPACK releases the GIL).
"""
from __future__ import annotations

import concurrent.futures
import math
import os

import numpy as np
import scipy.linalg as sla

from benchlib.reference import r_segment

SHARED = -1


def route(x, w_router, bias, cfg):
    """(idx (B, k), weight (B, k), margin (B,)) of the float64 router."""
    n_exp, n_group = cfg["n_routed_experts"], cfg["n_group"]
    keep, top_k = cfg["topk_group"], cfg["num_experts_per_tok"]
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                   @ np.asarray(w_router, np.float64).T)))
    choice = scores + np.asarray(bias, np.float64)
    per = n_exp // n_group
    b = len(x)
    idx = np.zeros((b, top_k), np.int64)
    weight = np.zeros((b, top_k))
    margin = np.full(b, np.inf)
    for t in range(b):
        groups = choice[t].reshape(n_group, per)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        order = np.argsort(-group_score, kind="stable")
        if keep < n_group:
            margin[t] = group_score[order[keep - 1]] - group_score[order[keep]]
        masked = np.zeros(n_exp)
        for g in order[:keep]:
            masked[g * per:(g + 1) * per] = groups[g]
        pick = np.argsort(-masked, kind="stable")
        if top_k < n_exp:
            margin[t] = min(margin[t], masked[pick[top_k - 1]] - masked[pick[top_k]])
        idx[t] = pick[:top_k]
        w = scores[t, idx[t]]
        if cfg["norm_topk_prob"]:
            w = w / (w.sum() + 1e-20)
        weight[t] = w * cfg["routed_scaling_factor"]
    return idx, weight, margin


class Block:
    """One column block of a matrix, mapped and cut into tiles."""

    def __init__(self, cfg, w_cols, w_scale, rows):
        """`w_cols` (fan_in, cols): the block's weights; `w_scale` the
        whole matrix's largest magnitude."""
        imac = cfg["imac"]
        tech = imac["technology"]
        g_on, g_off = 1.0 / tech["r_low"], 1.0 / tech["r_high"]
        wn = np.asarray(w_cols, np.float64) / w_scale
        self.k = (g_on - g_off) / w_scale
        self.imac = imac
        fan_in = wn.shape[0]
        self.hp = math.ceil(fan_in / rows)
        self.rows = math.ceil(fan_in / self.hp)
        self.tiles = []
        for g in (np.clip(g_off + np.maximum(wn, 0) * (g_on - g_off), g_off, g_on),
                  np.clip(g_off + np.maximum(-wn, 0) * (g_on - g_off), g_off, g_on)):
            padded = np.zeros((self.hp * self.rows, wn.shape[1]))  # pad cells: no device
            padded[:fan_in] = g
            self.tiles.append([padded[h * self.rows:(h + 1) * self.rows]
                               for h in range(self.hp)])

    def tasks(self, a):
        """The block's tile solves for digital input `a`, and the scale."""
        a = np.asarray(a, np.float64)
        scale = float(np.max(np.abs(a))) or 1.0
        v = np.zeros(self.hp * self.rows)
        v[: a.size] = a * self.imac["vdd"] / scale
        drives = [v[h * self.rows:(h + 1) * self.rows] for h in range(self.hp)]
        return [(tile, drives[h]) for side in self.tiles for h, tile in enumerate(side)], scale

    def finish(self, solved, scale):
        """(z (cols,), device power) from the solved tasks, in task order."""
        n = len(solved) // 2
        i_diff = sum(i for i, _ in solved[:n]) - sum(i for i, _ in solved[n:])
        z = i_diff * scale / (self.k * self.imac["vdd"])
        return z, float(sum(p for _, p in solved))


def chain_inverse(d, a):
    """Inverses of symmetric tridiagonal matrices with diagonals `d` (..., N)
    and every off-diagonal entry `a` (Thomas algorithm on the identity)."""
    n = d.shape[-1]
    eye = np.eye(n)
    cp = np.empty_like(d)
    bp = np.empty(d.shape + (n,))
    cp[..., 0] = a / d[..., 0]
    bp[..., 0, :] = eye[0] / d[..., 0, None]
    for j in range(1, n):
        den = d[..., j] - a * cp[..., j - 1]
        cp[..., j] = a / den
        bp[..., j, :] = (eye[j] - a * bp[..., j - 1, :]) / den[..., None]
    x = np.empty_like(bp)
    x[..., n - 1, :] = bp[..., n - 1, :]
    for j in range(n - 2, -1, -1):
        x[..., j, :] = bp[..., j, :] - cp[..., j, None] * x[..., j + 1, :]
    return x


def solve_tiles(g, v, r_seg, r_src, r_tia):
    """Exact DC solve of tiles g (T, M, N) driven by v (T, M) through r_src,
    columns into TIAs of r_tia: (i_out (T, N), power the sources deliver (T,)).

    Row nodes r(i, j), column nodes c(i, j), a wire segment of r_seg between
    neighbours on a line, the device g[i, j] between r(i, j) and c(i, j)
    (0: no device). Row i: A_i vr_i - G_i vc_i = g_s v_i e_0, with A_i the
    row's tridiagonal chain; so vr_i = A_i^-1 (G_i vc_i + g_s v_i e_0), and
    the column nodes solve S vc = b, S = C + G - blockdiag(G_i A_i^-1 G_i),
    C the column chains with the TIAs.
    """
    g = np.asarray(g, np.float64)
    v = np.asarray(v, np.float64)
    _, m, n = g.shape
    g_w, g_s, g_t = 1.0 / r_seg, 1.0 / r_src, 1.0 / r_tia

    def degree(k):
        deg = np.full(k, 2.0)
        deg[[0, -1]] = 1.0
        return deg if k > 1 else np.zeros(1)

    d = g + g_w * degree(n)
    d[..., 0] += g_s
    ainv = chain_inverse(d, -g_w)                             # (T, M, N, N)
    s = -(g[..., :, None] * ainv * g[..., None, :])
    diag = np.arange(n)
    s[..., diag, diag] += g_w * degree(m)[:, None] + g
    s[..., -1, diag, diag] += g_t
    rhs = g * ainv[..., :, 0] * (g_s * v)[..., None]          # (T, M, N)
    band = np.zeros((len(g), n + 1, m, n))                   # lower band storage
    for off in range(n):
        band[:, off, :, : n - off] = np.diagonal(s, offset=-off, axis1=-2, axis2=-1)
    band = band.reshape(len(g), n + 1, m * n)
    band[:, n, : (m - 1) * n] = -g_w
    i_out = np.empty((len(g), n))
    power = np.empty(len(g))
    for t in range(len(g)):
        _, x, info = sla.lapack.dpbsv(band[t], rhs[t].ravel(), lower=1)
        if info:
            raise np.linalg.LinAlgError(f"tile {t}: dpbsv info {info}")
        vc = x.reshape(m, n)
        # First row node of each row: row 0 of A_i^-1 (A_i is symmetric).
        vr_first = (np.einsum("ik,ik->i", ainv[t, :, 0, :], g[t] * vc)
                    + g_s * v[t] * ainv[t, :, 0, 0])
        i_out[t] = g_t * vc[-1]
        power[t] = np.sum(g_s * v[t] * (v[t] - vr_first))
    return i_out, power


def solve_blocks(cfg, jobs):
    """[(Block, input)] -> [(z, device power)]; blocks solved on threads."""
    imac = cfg["imac"]
    r_seg = r_segment(imac["interconnect"])

    def solve(job):
        block, a = job
        tasks, scale = block.tasks(a)
        out = []
        for at in range(0, len(tasks), 16):  # bounds the chains' inverses
            part = tasks[at:at + 16]
            i_out, p = solve_tiles(np.stack([t for t, _ in part]),
                                   np.stack([dr for _, dr in part]), r_seg,
                                   imac["r_source_ohm"], imac["r_tia_ohm"])
            out += list(zip(i_out, p))
        return block.finish(out, scale)

    workers = max(1, min(8, os.cpu_count() or 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        return list(pool.map(solve, jobs))
