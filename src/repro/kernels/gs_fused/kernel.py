"""Pallas TPU kernel: the *entire* block Gauss–Seidel sweep loop, fused.

This extends the Thomas tile in `repro.kernels.tridiag.kernel` from one
half-sweep to the whole iteration: instead of materializing the row/col
voltages to HBM between every half-sweep (two kernel launches + four
HBM round-trips per sweep in the ``"pallas"`` backend), one grid step
owns a lane block of LB independent (tile × sample) systems in VMEM and
iterates

    row-tridiag → transpose → col-tridiag → SOR update → residual

``sweeps`` times on-chip, then runs one final un-relaxed sweep so the
returned row voltages are consistent with the converged column voltages
(matching `repro.core.solver._sweep_solve` exactly).

Layouts. Column-phase arrays live in the natural ``(LB, M, N)`` layout:
the Thomas recurrence walks the sublane axis (M) while the vector unit
solves all N columns × LB systems per step. Row-phase arrays live in the
transposed ``(LB, N, M)`` layout for the same reason. The two in-VMEM
transposes per sweep (rhs in, solution out) replace what used to be HBM
round-trips.

Arithmetic split. The tridiagonal *coefficients* (diagonal chain +
device conductances + companion-stamp shunts) are constant across
sweeps — only the right-hand side changes. The forward-elimination
multipliers ``cp`` and inverse denominators ``inv_den`` are therefore
precomputed once outside the kernel (repro.kernels.gs_fused.ops), which
halves the sequential work per sweep and removes every division from
the inner loop.

VMEM budget: ~17 lane-block buffers of the padded tile, i.e. roughly
``17 × LB × pad8(M) × pad128(N) × 4B`` for f32 — `ops.fused_lane_block`
picks LB against an 8 MB budget (LB=30 at 32×32) and reports 0 when
even LB=1 does not fit (≥ ~352×352 tiles), in which case the solver
falls back to the per-half-sweep ``"pallas"`` backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gs_fused_kernel(
    # inputs
    g_ref,       # (LB, M, N) device conductances
    src_ref,     # (LB, M, N) row rhs sources (driver + i_inj_row)
    injc_ref,    # (LB, M, N) column rhs injections (i_inj_col)
    cpr_ref,     # (LB, N, M) row-system forward multipliers, transposed
    idr_ref,     # (LB, N, M) row-system inverse denominators, transposed
    cpc_ref,     # (LB, M, N) col-system forward multipliers
    idc_ref,     # (LB, M, N) col-system inverse denominators
    odr_ref,     # (LB, 1, 1) row off-diagonal (-g_row)
    odc_ref,     # (LB, 1, 1) col off-diagonal (-g_col)
    om_ref,      # (LB, 1, 1) SOR over-relaxation factor
    vc0_ref,     # (LB, M, N) initial column voltages (warm start)
    # outputs
    vr_ref,      # (LB, M, N) row-node voltages
    vc_ref,      # (LB, M, N) column-node voltages
    res_ref,     # (LB, 1, 1) last sweep's max |Δvc|
    # scratch
    btr_ref,     # (LB, N, M) transposed row rhs
    dpr_ref,     # (LB, N, M) row forward-sweep partials
    xr_ref,      # (LB, N, M) row solution (transposed)
    bc_ref,      # (LB, M, N) column rhs
    dpc_ref,     # (LB, M, N) col forward-sweep partials
    vcg_ref,     # (LB, M, N) col solution (the GS update)
    vcs_ref,     # (LB, M, N) SOR-relaxed column-voltage carry
    *,
    m: int,
    n: int,
    sweeps: int,
):
    g = g_ref[...]
    odr = odr_ref[...][:, :, 0]   # (LB, 1): broadcasts over (LB, M)
    odc = odc_ref[...][:, :, 0]
    omega = om_ref[...]
    vcs_ref[...] = vc0_ref[...]
    res_ref[...] = jnp.full(res_ref.shape, jnp.inf, res_ref.dtype)

    def row_solve():
        """All-rows Thomas solve given the current column voltages."""
        btr_ref[...] = jnp.swapaxes(g * vcs_ref[...] + src_ref[...], 1, 2)
        dpr_ref[:, 0, :] = btr_ref[:, 0, :] * idr_ref[:, 0, :]

        def fwd(j, _):
            dpr_ref[:, j, :] = (
                btr_ref[:, j, :] - odr * dpr_ref[:, j - 1, :]
            ) * idr_ref[:, j, :]
            return 0

        jax.lax.fori_loop(1, n, fwd, 0)
        xr_ref[:, n - 1, :] = dpr_ref[:, n - 1, :]

        def bwd(k, _):
            j = n - 2 - k
            xr_ref[:, j, :] = (
                dpr_ref[:, j, :] - cpr_ref[:, j, :] * xr_ref[:, j + 1, :]
            )
            return 0

        jax.lax.fori_loop(0, n - 1, bwd, 0)
        return jnp.swapaxes(xr_ref[...], 1, 2)  # (LB, M, N)

    def col_solve(vr):
        """All-columns Thomas solve given fresh row voltages."""
        bc_ref[...] = g * vr + injc_ref[...]
        dpc_ref[:, 0, :] = bc_ref[:, 0, :] * idc_ref[:, 0, :]

        def fwd(i, _):
            dpc_ref[:, i, :] = (
                bc_ref[:, i, :] - odc * dpc_ref[:, i - 1, :]
            ) * idc_ref[:, i, :]
            return 0

        jax.lax.fori_loop(1, m, fwd, 0)
        vcg_ref[:, m - 1, :] = dpc_ref[:, m - 1, :]

        def bwd(k, _):
            i = m - 2 - k
            vcg_ref[:, i, :] = (
                dpc_ref[:, i, :] - cpc_ref[:, i, :] * vcg_ref[:, i + 1, :]
            )
            return 0

        jax.lax.fori_loop(0, m - 1, bwd, 0)
        return vcg_ref[...]

    def sweep(_, __):
        vr = row_solve()
        vc_old = vcs_ref[...]
        vc_gs = col_solve(vr)
        vc_new = vc_old + omega * (vc_gs - vc_old)
        vcs_ref[...] = vc_new
        # One axis at a time: Mosaic aborts the process (a layout check
        # failure, not a Python error) on a two-axis keepdims reduction.
        delta = jnp.abs(vc_new - vc_old)
        res_ref[...] = jnp.max(
            jnp.max(delta, axis=2, keepdims=True), axis=1, keepdims=True
        )
        return 0

    jax.lax.fori_loop(0, sweeps, sweep, 0)

    # Final un-relaxed half-sweeps: row voltages consistent with vc.
    vr = row_solve()
    vr_ref[...] = vr
    vc_ref[...] = col_solve(vr)


@functools.partial(
    jax.jit, static_argnames=("m", "n", "sweeps", "lane_block", "interpret")
)
def gs_fused_nb(
    g: jax.Array,        # (B, M, N)
    src_row: jax.Array,  # (B, M, N)
    inj_col: jax.Array,  # (B, M, N)
    cp_rowT: jax.Array,  # (B, N, M)
    id_rowT: jax.Array,  # (B, N, M)
    cp_col: jax.Array,   # (B, M, N)
    id_col: jax.Array,   # (B, M, N)
    od_row: jax.Array,   # (B, 1, 1)
    od_col: jax.Array,   # (B, 1, 1)
    omega: jax.Array,    # (B, 1, 1)
    vc0: jax.Array,      # (B, M, N)
    *,
    m: int,
    n: int,
    sweeps: int,
    lane_block: int,
    interpret: bool = False,
) -> "tuple[jax.Array, jax.Array, jax.Array]":
    """Dispatch the fused sweep kernel over a padded batch.

    B must be a multiple of ``lane_block``; zero-padded trailing systems
    are harmless (all-zero coefficients solve to all-zero voltages).

    Returns:
      (vr, vc, res): (B, M, N), (B, M, N), (B, 1, 1).
    """
    batch = g.shape[0]
    assert batch % lane_block == 0, (batch, lane_block)
    grid = (batch // lane_block,)
    dtype = g.dtype

    def spec(rows, cols):
        return pl.BlockSpec((lane_block, rows, cols), lambda i: (i, 0, 0))

    mn, nm, one = spec(m, n), spec(n, m), spec(1, 1)
    return pl.pallas_call(
        functools.partial(_gs_fused_kernel, m=m, n=n, sweeps=sweeps),
        grid=grid,
        in_specs=[mn, mn, mn, nm, nm, mn, mn, one, one, one, mn],
        out_specs=[mn, mn, one],
        out_shape=[
            jax.ShapeDtypeStruct((batch, m, n), dtype),
            jax.ShapeDtypeStruct((batch, m, n), dtype),
            jax.ShapeDtypeStruct((batch, 1, 1), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((lane_block, n, m), dtype),
            pltpu.VMEM((lane_block, n, m), dtype),
            pltpu.VMEM((lane_block, n, m), dtype),
            pltpu.VMEM((lane_block, m, n), dtype),
            pltpu.VMEM((lane_block, m, n), dtype),
            pltpu.VMEM((lane_block, m, n), dtype),
            pltpu.VMEM((lane_block, m, n), dtype),
        ],
        interpret=interpret,
    )(
        g, src_row, inj_col, cp_rowT, id_rowT, cp_col, id_col,
        od_row, od_col, omega, vc0,
    )
