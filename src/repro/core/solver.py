"""Crossbar DC circuit solver — the "SPICE engine" of IMAC-Sim-JAX.

A crossbar partition with M row wires and N column wires, per-segment wire
resistance, row drivers behind a source resistance and column TIAs
(virtual grounds) forms a 2MN-node linear resistive network (the neuron
nonlinearity sits *behind* the TIA, so each layer's crossbar solve is
linear — nonlinearity is applied between layers, exactly as IMAC-Sim's
behavioural neuron subcircuits do).

Structure exploited: holding the column node voltages fixed, every row is
an independent tridiagonal system (wire chain + memristor loads);
symmetrically for columns. Alternating batched tridiagonal (Thomas) solves
is a block Gauss–Seidel on a symmetric diagonally-dominant M-matrix and
converges geometrically. All rows × all tiles × all samples solve in one
batched kernel — this is what makes the simulator TPU-native where SPICE
is one-netlist-at-a-time.

`solve_dense_mna` is the small-array oracle (full MNA matrix +
jnp.linalg.solve) used by tests and by the SPICE-netlist round-trip.

The solve itself is pluggable through the named backend registry in
`repro.core.backends` (selected via `SolveOptions` or the
``REPRO_SOLVER_BACKEND`` env var):

  * ``"scan"``  — lax.scan Thomas inner solve (reference, default);
  * ``"pallas"`` — Pallas Thomas tile per half-sweep
    (`repro.kernels.tridiag`);
  * ``"fused"`` — one Pallas kernel runs the *entire* sweep loop in
    VMEM (`repro.kernels.gs_fused`);
  * any `TridiagFn` callable — a custom inner solve.

Companion-model stamps (node-capacitor conductances / history currents
of a transient step, warm-start voltages) enter through the frozen
`Stamps` pytree rather than loose kwargs; the old per-field kwargs are
accepted for one release behind a DeprecationWarning.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.backends import (
    SolverBackend,
    TridiagFn,
    get_backend,
)
from repro.core.partition import ordered_sum


@dataclasses.dataclass(frozen=True)
class CircuitParams:
    """Electrical parameters of one crossbar tile's periphery + wires.

    Attributes:
      r_row: row-wire resistance per bitcell segment (ohms).
      r_col: column-wire resistance per segment (ohms).
      r_source: row driver output resistance (ohms).
      r_tia: TIA input resistance / virtual-ground quality (ohms).
      gs_iters: block Gauss–Seidel sweeps (fixed for jit).
      omega: SOR over-relaxation factor (1.0 = plain Gauss–Seidel;
        ~1.8 roughly quadruples the convergence rate on large tiles).
    """

    r_row: float = 13.8
    r_col: float = 13.8
    r_source: float = 100.0
    r_tia: float = 10.0
    gs_iters: int = 64
    omega: float = 1.8
    tol: float = 0.0  # >0: stop sweeping once max |Δvc| < tol (volts)

    @property
    def g_row(self) -> float:
        return 1.0 / self.r_row

    @property
    def g_col(self) -> float:
        return 1.0 / self.r_col

    @property
    def g_source(self) -> float:
        return 1.0 / self.r_source

    @property
    def g_tia(self) -> float:
        return 1.0 / self.r_tia


class CrossbarSolution(NamedTuple):
    """Solved node voltages and outputs of a crossbar tile batch."""

    i_out: jax.Array   # (..., N) column currents into the TIAs
    vr: jax.Array      # (..., M, N) row-wire node voltages
    vc: jax.Array      # (..., M, N) column-wire node voltages
    residual: jax.Array  # scalar-ish (...) final GS update magnitude
    # Gauss–Seidel sweeps actually run: the while_loop trip count under
    # tol-based early exit, else the static gs_iters budget. A scalar
    # (the early-exit condition is a batch max, so the whole batch
    # sweeps together) — solver telemetry rides this aux output out of
    # jit instead of host callbacks (see repro.obs).
    sweeps: jax.Array = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Stamps:
    """Companion-model stamps added to the crossbar MNA assembly.

    A frozen dataclass registered as a JAX pytree — it crosses jit
    boundaries and lax control flow like any array container. All fields
    are optional (None = absent) and, when present, broadcast against
    the solve's ``(..., M, N)`` batch:

    Attributes:
      g_shunt_row: per-node extra conductance to ground on row-wire
        nodes — e.g. C/dt (BE) or 2C/dt (trapezoidal) of a node
        capacitor in an implicit transient step.
      g_shunt_col: same, on column-wire nodes.
      i_inj_row: per-node current injection into row-wire nodes — the
        companion history source of the discretized capacitor.
      i_inj_col: same, into column-wire nodes.
      v_init: initial column-node voltages — warm-starts the
        Gauss–Seidel iteration (the previous time step's solution),
        which is what makes few sweeps per transient step sufficient.
    """

    g_shunt_row: Optional[jax.Array] = None
    g_shunt_col: Optional[jax.Array] = None
    i_inj_row: Optional[jax.Array] = None
    i_inj_col: Optional[jax.Array] = None
    v_init: Optional[jax.Array] = None

    def fields(self) -> "tuple[Optional[jax.Array], ...]":
        return (
            self.g_shunt_row,
            self.g_shunt_col,
            self.i_inj_row,
            self.i_inj_col,
            self.v_init,
        )


@dataclasses.dataclass(frozen=True)
class SolveOptions:
    """Static solver configuration (how to run, not what to solve).

    Attributes:
      backend: a registry name (``"scan"``, ``"pallas"``, ``"fused"``),
        a `repro.core.backends.SolverBackend`, a bare `TridiagFn`
        callable (custom inner solve), or None for the process default
        (``$REPRO_SOLVER_BACKEND``, else ``"scan"``).
      interpret: force Pallas interpret mode on/off; None = automatic
        (interpret off-TPU, with a single logged notice).
      shard_axis: mesh axis name when the solve runs inside a
        `shard_map` over a leading batch axis (sharded sweeps,
        repro.distributed.sweep). The tol early-exit condition is a
        *batch-global* residual max; under sharding it must be pmax'ed
        across shards so every shard runs the same number of sweeps —
        that is what keeps sharded results bitwise-identical to the
        unsharded solve. None = no cross-shard reduction (default).
    """

    backend: Union[str, SolverBackend, TridiagFn, None] = None
    interpret: Optional[bool] = None
    shard_axis: Optional[str] = None

    def resolved(self) -> SolverBackend:
        return get_backend(self.backend)


DEFAULT_OPTIONS = SolveOptions()


def tridiag_scan(dl: jax.Array, d: jax.Array, du: jax.Array, b: jax.Array) -> jax.Array:
    """Batched Thomas algorithm along the last axis via lax.scan.

    Args:
      dl: (..., N) sub-diagonal (dl[..., 0] ignored).
      d:  (..., N) diagonal.
      du: (..., N) super-diagonal (du[..., N-1] ignored).
      b:  (..., N) right-hand side.

    Returns:
      x: (..., N) solution.
    """
    n = d.shape[-1]
    if n == 1:
        return b / d
    # Move the system axis to the front for scan: (N, batch...).
    dl_t = jnp.moveaxis(dl, -1, 0)
    d_t = jnp.moveaxis(d, -1, 0)
    du_t = jnp.moveaxis(du, -1, 0)
    b_t = jnp.moveaxis(b, -1, 0)

    def fwd(carry, row):
        cp_prev, dp_prev = carry
        dl_j, d_j, du_j, b_j = row
        denom = d_j - dl_j * cp_prev
        cp = du_j / denom
        dp = (b_j - dl_j * dp_prev) / denom
        return (cp, dp), (cp, dp)

    zeros = jnp.zeros_like(d_t[0])
    # First row has no sub-diagonal coupling.
    dl_eff = dl_t.at[0].set(0.0)
    (_, _), (cp, dp) = jax.lax.scan(fwd, (zeros, zeros), (dl_eff, d_t, du_t, b_t))

    def bwd(x_next, row):
        cp_j, dp_j = row
        x_j = dp_j - cp_j * x_next
        return x_j, x_j

    _, x_rev = jax.lax.scan(bwd, zeros, (cp, dp), reverse=True)
    return jnp.moveaxis(x_rev, 0, -1)


def _align(value, ndim: int, dtype=None) -> jax.Array:
    """Align a circuit scalar against an array of rank `ndim`.

    Electrical parameters may be python floats (one tile family) or
    arrays with leading batch axes — e.g. a (C,) vector of per-config
    resistances in a batched design-space sweep. Leading-axis arrays are
    reshaped so their axes line up with the target's *leading* axes and
    broadcast over the rest.
    """
    v = jnp.asarray(value, dtype)
    if v.ndim == 0 or v.ndim == ndim:
        return v
    return v.reshape(v.shape + (1,) * (ndim - v.ndim))


def _row_system(
    g: jax.Array,
    vc: jax.Array,
    v_in: jax.Array,
    cp: CircuitParams,
    g_shunt=None,
    i_inj=None,
):
    """Tridiagonal systems for all rows given column voltages.

    g, vc: (..., M, N); v_in: (..., M). Systems run along N.
    `g_shunt`/`i_inj` add a per-node conductance to ground / current
    injection — the companion-model stamps of node capacitors in a
    transient step (repro.transient); both default to absent (pure DC).
    """
    n = g.shape[-1]
    dtype = g.dtype
    g_row = _align(cp.g_row, g.ndim, dtype)
    g_source = _align(cp.g_source, g.ndim, dtype)
    if n == 1:
        chain = g_source
    else:
        idx = jnp.arange(n)
        chain = jnp.where(
            idx == 0,
            g_row + g_source,
            jnp.where(idx == n - 1, g_row, 2.0 * g_row),
        )
    d = chain + g
    if g_shunt is not None:
        d = d + g_shunt
    off = jnp.broadcast_to(-g_row, g.shape)
    dl = off
    du = off
    b = g * vc
    b = b.at[..., 0].add(_align(cp.g_source, g.ndim - 1, dtype) * v_in)
    if i_inj is not None:
        b = b + i_inj
    return dl, d, du, b


def _col_system(
    g: jax.Array,
    vr: jax.Array,
    cp: CircuitParams,
    g_shunt=None,
    i_inj=None,
):
    """Tridiagonal systems for all columns given row voltages.

    Transposed view: systems run along M. g, vr: (..., M, N);
    `g_shunt`/`i_inj` are per-node capacitor companion stamps in the
    untransposed (..., M, N) layout. Returns arrays shaped (..., N, M).
    """
    m = g.shape[-2]
    dtype = g.dtype
    gt = jnp.swapaxes(g, -1, -2)     # (..., N, M)
    vrt = jnp.swapaxes(vr, -1, -2)
    g_col = _align(cp.g_col, gt.ndim, dtype)
    g_tia = _align(cp.g_tia, gt.ndim, dtype)
    if m == 1:
        chain = g_tia
    else:
        idx = jnp.arange(m)
        chain = jnp.where(
            idx == 0,
            g_col,
            jnp.where(idx == m - 1, g_col + g_tia, 2.0 * g_col),
        )
    d = chain + gt
    if g_shunt is not None:
        d = d + jnp.swapaxes(g_shunt, -1, -2)
    off = jnp.broadcast_to(-g_col, gt.shape)
    dl = off
    du = off
    b = gt * vrt  # TIA node is grounded: no extra rhs term.
    if i_inj is not None:
        b = b + jnp.swapaxes(i_inj, -1, -2)
    return dl, d, du, b


def _merge_deprecated(
    tridiag,
    stamps: Optional[Stamps],
    options: Optional[SolveOptions],
    legacy: dict,
) -> "tuple[Optional[Stamps], SolveOptions]":
    """One-release deprecation shim: warn and forward the old kwargs.

    The pre-registry API passed companion stamps as five loose kwargs
    and the inner solve as a raw ``tridiag=`` callable. Both still work
    (covered by tests/test_backends.py) but emit a DeprecationWarning;
    mixing old and new spellings of the same thing is an error.
    """
    used = {k: v for k, v in legacy.items() if v is not None}
    if used:
        if stamps is not None:
            raise ValueError(
                f"pass companion stamps either as Stamps or as the "
                f"deprecated kwargs {sorted(used)}, not both"
            )
        warnings.warn(
            f"solve_crossbar kwargs {sorted(used)} are deprecated; pass "
            "stamps=Stamps(...) instead (one-release shim)",
            DeprecationWarning,
            stacklevel=3,
        )
        stamps = Stamps(**used)
    if tridiag is not None:
        if options is not None and options.backend is not None:
            raise ValueError(
                "pass the solver either as options=SolveOptions(backend=...) "
                "or as the deprecated tridiag= callable, not both"
            )
        warnings.warn(
            "solve_crossbar's tridiag= argument is deprecated; pass "
            "options=SolveOptions(backend=<name or TridiagFn>) instead "
            "(one-release shim)",
            DeprecationWarning,
            stacklevel=3,
        )
        options = dataclasses.replace(
            options or DEFAULT_OPTIONS, backend=tridiag
        )
    return stamps, options or DEFAULT_OPTIONS


def solve_crossbar(
    g: jax.Array,
    v_in: jax.Array,
    cp: CircuitParams,
    tridiag: Optional[TridiagFn] = None,
    *,
    stamps: Optional[Stamps] = None,
    options: Optional[SolveOptions] = None,
    g_shunt_row: "jax.Array | None" = None,
    g_shunt_col: "jax.Array | None" = None,
    i_inj_row: "jax.Array | None" = None,
    i_inj_col: "jax.Array | None" = None,
    v_init: "jax.Array | None" = None,
) -> CrossbarSolution:
    """Solve crossbar tiles (DC, or one implicit transient step).

    The electrical fields of `cp` may be python floats or arrays with
    leading batch axes aligned to g's leading axes — a design-space sweep
    passes (C,) per-config resistances so C configurations share one
    solve (and one compilation) with a single while_loop; `gs_iters` and
    `tol` stay static.

    With optional companion-model stamps (`stamps`) this same assembly
    solves one implicit time step of the parasitic-RC network: a node
    capacitor C discretized by backward-Euler/trapezoidal becomes a
    conductance to ground (`Stamps.g_shunt_*`, C/dt or 2C/dt) plus a
    history current source (`Stamps.i_inj_*`) — see
    repro.transient.integrator. `Stamps.v_init` warm-starts the
    Gauss–Seidel iteration (the previous time step's column voltages),
    which is what makes few sweeps per step sufficient.

    How the solve runs is chosen by `options` (see `SolveOptions` and
    repro.core.backends): the ``"scan"`` and ``"pallas"`` backends drive
    the sweep loop here with a batched inner tridiagonal solve; the
    ``"fused"`` backend hands the whole loop to one Pallas kernel that
    keeps the systems resident in VMEM across sweeps. The fused backend
    runs the full `gs_iters` budget (no `tol` early exit — on-chip
    sweeps are cheap); `tol` still applies to the other backends.

    Args:
      g: (..., M, N) memristor conductances (S). 0 = absent device.
      v_in: (..., M) driver voltages behind r_source.
      cp: circuit parameters.
      tridiag: DEPRECATED — raw inner-solve callable; use
        ``options=SolveOptions(backend=...)``.
      stamps: optional companion-model stamps / warm start.
      options: backend selection and Pallas interpret override.
      g_shunt_row / g_shunt_col / i_inj_row / i_inj_col / v_init:
        DEPRECATED loose spellings of the `Stamps` fields (one-release
        shim; warns and forwards).

    Returns:
      CrossbarSolution; i_out[..., j] = current into column j's TIA.
    """
    stamps, options = _merge_deprecated(
        tridiag,
        stamps,
        options,
        dict(
            g_shunt_row=g_shunt_row,
            g_shunt_col=g_shunt_col,
            i_inj_row=i_inj_row,
            i_inj_col=i_inj_col,
            v_init=v_init,
        ),
    )
    backend = options.resolved()
    if backend.make_solve is not None:
        return backend.make_solve(options)(g, v_in, cp, stamps)
    return _sweep_solve(
        g, v_in, cp, backend.make_tridiag(options), stamps,
        shard_axis=options.shard_axis,
    )


def _sweep_solve(
    g: jax.Array,
    v_in: jax.Array,
    cp: CircuitParams,
    tridiag: TridiagFn,
    stamps: Optional[Stamps],
    shard_axis: Optional[str] = None,
) -> CrossbarSolution:
    """The generic sweep loop: batched inner tridiag + SOR in jnp."""
    st = stamps or Stamps()
    g_shunt_row, g_shunt_col = st.g_shunt_row, st.g_shunt_col
    i_inj_row, i_inj_col, v_init = st.i_inj_row, st.i_inj_col, st.v_init
    g = jnp.asarray(g)
    v_in = jnp.asarray(v_in)
    m, n = g.shape[-2], g.shape[-1]
    # Broadcast conductances and drives to a common batch shape so the
    # loop carry and scan carries have fixed shapes.
    batch = jnp.broadcast_shapes(
        g.shape[:-2],
        v_in.shape[:-1],
        *(
            x.shape[:-2]
            for x in (g_shunt_row, g_shunt_col, i_inj_row, i_inj_col, v_init)
            if x is not None
        ),
    )
    g = jnp.broadcast_to(g, batch + (m, n))
    v_in = jnp.broadcast_to(v_in, batch + (m,))
    vc0 = (
        jnp.zeros_like(g)
        if v_init is None
        else jnp.broadcast_to(v_init.astype(g.dtype), g.shape)
    )
    omega = _align(cp.omega, g.ndim, g.dtype)

    def sweep(vc):
        dl, d, du, b = _row_system(g, vc, v_in, cp, g_shunt_row, i_inj_row)
        vr = tridiag(dl, d, du, b)
        dl, d, du, b = _col_system(g, vr, cp, g_shunt_col, i_inj_col)
        vct = tridiag(dl, d, du, b)
        return vr, jnp.swapaxes(vct, -1, -2)

    res0 = jnp.full(batch, jnp.inf, g.dtype)

    if cp.tol > 0.0:
        # Early-exit sweeps: most samples/tiles converge well before the
        # worst-case bound (§Perf solver iteration — ~2-3x fewer sweeps
        # on the 32x32 Table-III workload).
        def w_cond(carry):
            _, res, i = carry
            rmax = jnp.max(res)
            if shard_axis is not None:
                # Inside shard_map the batch max only sees the local
                # shard; pmax restores the batch-global trip count so
                # sharded and unsharded solves sweep in lockstep.
                rmax = jax.lax.pmax(rmax, shard_axis)
            return jnp.logical_and(i < cp.gs_iters, rmax > cp.tol)

        def w_body(carry):
            vc, _, i = carry
            vr, vc_gs = sweep(vc)
            vc_new = vc + omega * (vc_gs - vc)
            res = jnp.max(jnp.abs(vc_new - vc), axis=(-1, -2))
            return vc_new, res, i + 1

        vc, residual, sweeps = jax.lax.while_loop(
            w_cond, w_body, (vc0, res0, jnp.zeros((), jnp.int32))
        )
    else:
        def body(_, carry):
            vc, _ = carry
            vr, vc_gs = sweep(vc)
            vc_new = vc + omega * (vc_gs - vc)
            res = jnp.max(jnp.abs(vc_new - vc), axis=(-1, -2))
            return vc_new, res

        vc, residual = jax.lax.fori_loop(0, cp.gs_iters, body, (vc0, res0))
        sweeps = jnp.asarray(cp.gs_iters, jnp.int32)
    vr, vc = sweep(vc)  # final row solve consistent with converged vc
    i_out = _align(cp.g_tia, vc.ndim - 1, g.dtype) * vc[..., m - 1, :]
    return CrossbarSolution(
        i_out=i_out, vr=vr, vc=vc, residual=residual, sweeps=sweeps
    )


def suggest_iters(m: int, n: int) -> int:
    """Sweeps needed for <~1e-3 relative output error (empirical; see
    tests/test_solver.py). The GS rate degrades as total device
    conductance per line approaches the wire conductance, which scales
    with line length."""
    return max(48, int(0.75 * max(m, n)))


def solve_ideal(g: jax.Array, v_in: jax.Array) -> jax.Array:
    """Ideal crossbar (no parasitics): i_out = g^T v. (..., M, N) x (..., M)."""
    return jnp.einsum(
        "...mn,...m->...n", g, v_in, precision=jax.lax.Precision.HIGHEST
    )


# ---------------------------------------------------------------------------
# Dense MNA oracle (small arrays; used by tests + netlist round-trip).
# ---------------------------------------------------------------------------


def _mna_matrix(g, v_in, cp: CircuitParams, stamps: "Stamps | None" = None):
    """Assemble the full (2MN, 2MN) conductance matrix and RHS.

    Node order: row nodes r(i,j) = i*N+j, then column nodes
    c(i,j) = M*N + i*N + j. Ground (TIA virtual ground, source return) is
    eliminated. Optional `stamps` add the transient companion model
    (per-node shunt conductances on the diagonal, history-current
    injections on the RHS), so an implicit integrator step can be
    oracle-checked against the same dense assembly as the DC solve.
    """
    m, n = g.shape
    nn = 2 * m * n
    a = jnp.zeros((nn, nn), g.dtype)
    rhs = jnp.zeros((nn,), g.dtype)

    def r_idx(i, j):
        return i * n + j

    def c_idx(i, j):
        return m * n + i * n + j

    def stamp(a, p, q, cond):
        a = a.at[p, p].add(cond)
        a = a.at[q, q].add(cond)
        a = a.at[p, q].add(-cond)
        a = a.at[q, p].add(-cond)
        return a

    # Row wires.
    for i in range(m):
        for j in range(n - 1):
            a = stamp(a, r_idx(i, j), r_idx(i, j + 1), cp.g_row)
    # Column wires.
    for j in range(n):
        for i in range(m - 1):
            a = stamp(a, c_idx(i, j), c_idx(i + 1, j), cp.g_col)
    # Memristors.
    for i in range(m):
        for j in range(n):
            a = stamp(a, r_idx(i, j), c_idx(i, j), g[i, j])
    # Sources (to v_in through g_source) and TIAs (to ground through g_tia).
    for i in range(m):
        p = r_idx(i, 0)
        a = a.at[p, p].add(cp.g_source)
        rhs = rhs.at[p].add(cp.g_source * v_in[i])
    for j in range(n):
        p = c_idx(m - 1, j)
        a = a.at[p, p].add(cp.g_tia)
    if stamps is not None:
        mn = m * n
        diag = jnp.arange(nn)
        for block, g_sh, i_inj in (
            (slice(0, mn), stamps.g_shunt_row, stamps.i_inj_row),
            (slice(mn, nn), stamps.g_shunt_col, stamps.i_inj_col),
        ):
            if g_sh is not None:
                flat = jnp.broadcast_to(
                    jnp.asarray(g_sh, g.dtype), (m, n)
                ).reshape(mn)
                a = a.at[diag[block], diag[block]].add(flat)
            if i_inj is not None:
                flat = jnp.broadcast_to(
                    jnp.asarray(i_inj, g.dtype), (m, n)
                ).reshape(mn)
                rhs = rhs.at[block].add(flat)
    return a, rhs


def mna_system(
    g: jax.Array,
    v_in: jax.Array,
    cp: CircuitParams,
    stamps: "Stamps | None" = None,
) -> "tuple[jax.Array, jax.Array]":
    """Public dense-MNA assembly of one tile: (A, rhs) with A (2MN, 2MN).

    Node order: row nodes r(i,j) = i*N+j first, then column nodes
    c(i,j) = M*N + i*N + j — the same order `node_capacitances` in
    repro.transient.integrator flattens to, so the transient dense oracle
    (C dv/dt = rhs - A v) can be built directly from these stamps.
    Optional `stamps` add the companion model of one implicit step.
    """
    return _mna_matrix(jnp.asarray(g), jnp.asarray(v_in), cp, stamps)


def solve_dense_mna(
    g: jax.Array,
    v_in: jax.Array,
    cp: CircuitParams,
    stamps: "Stamps | None" = None,
) -> CrossbarSolution:
    """Oracle: full MNA solve of one tile. g: (M, N), v_in: (M,).

    With `stamps`, solves the companion system of one implicit transient
    step instead of the DC operating point (`Stamps.v_init` is ignored —
    the dense solve needs no warm start).
    """
    g = jnp.asarray(g)
    v_in = jnp.asarray(v_in)
    m, n = g.shape
    a, rhs = _mna_matrix(g, v_in, cp, stamps)
    x = jnp.linalg.solve(a, rhs)
    vr = x[: m * n].reshape(m, n)
    vc = x[m * n :].reshape(m, n)
    i_out = cp.g_tia * vc[m - 1, :]
    return CrossbarSolution(
        i_out=i_out,
        vr=vr,
        vc=vc,
        residual=jnp.zeros(()),
        sweeps=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Power extraction from a solved tile.
# ---------------------------------------------------------------------------


def crossbar_power(
    g: jax.Array,
    v_in: jax.Array,
    sol: CrossbarSolution,
    cp: CircuitParams,
) -> jax.Array:
    """Total dissipated power (W) of solved tiles; reduces last two dims.

    Sums run in `ordered_sum`'s fixed order, so a tile's power has the
    same bits whatever batch it is solved in.
    """
    def sum2(x):
        return ordered_sum(ordered_sum(x, axis=-1), axis=-1)

    vr, vc = sol.vr, sol.vc
    p_dev = sum2(g * (vr - vc) ** 2)
    ndim = p_dev.ndim
    dr = jnp.diff(vr, axis=-1)
    p_row = _align(cp.g_row, ndim, dr.dtype) * sum2(dr**2)
    dc = jnp.diff(vc, axis=-2)
    p_col = _align(cp.g_col, ndim, dc.dtype) * sum2(dc**2)
    p_src = _align(cp.g_source, ndim, vr.dtype) * ordered_sum(
        (v_in - vr[..., :, 0]) ** 2
    )
    p_tia = _align(cp.g_tia, ndim, vc.dtype) * ordered_sum(vc[..., -1, :] ** 2)
    return p_dev + p_row + p_col + p_src + p_tia


def ideal_power(g: jax.Array, v_in: jax.Array) -> jax.Array:
    """Power of the ideal crossbar (columns at virtual ground)."""
    return jnp.einsum(
        "...mn,...m->...", g, v_in**2, precision=jax.lax.Precision.HIGHEST
    )
