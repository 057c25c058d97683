"""Lowering tests: parsed netlists -> MNA structures (repro.spice.lower).

The tight (1e-6-relative) parser-vs-dense-MNA comparisons run under
`jax.enable_x64(True)`: ideal crossbars lower with R_WIRE_EPS
wire segments (1e-6 ohm), which makes the float32 dense solve
ill-conditioned while the float64 one is exact to round-off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.devices import MRAM
from repro.core.imac import IMACConfig, build_plans
from repro.core.mapping import map_network
from repro.core.netlist import map_imac
from repro.core.solver import CircuitParams, Stamps, solve_crossbar, solve_dense_mna
from repro.spice import (
    NonCrossbarError,
    UnsupportedElementError,
    flatten,
    lower,
    lower_crossbar,
    lower_network,
    parse_netlist,
    solve_dc,
)
from repro.spice.lower import R_WIRE_EPS


# ---------------------------------------------------------------------------
# Netlist builders (plain text, third-party style names).
# ---------------------------------------------------------------------------


def ideal_crossbar(g, v, r_source=100.0, r_tia=10.0):
    """Bare crossbar: driver -> Rsource -> row node; devices bridge row
    nodes straight to column nodes; TIA to ground."""
    m, n = g.shape
    lines = ["* ideal crossbar"]
    for i in range(m):
        lines.append(f"Vdrive{i} d{i} 0 DC {v[i]}")
        lines.append(f"Rsrc{i} d{i} row{i} {r_source}")
    for i in range(m):
        for j in range(n):
            if g[i, j] > 0:
                lines.append(f"Rdev{i}_{j} row{i} col{j} {1.0 / g[i, j]}")
    for j in range(n):
        lines.append(f"Rtia{j} col{j} 0 {r_tia}")
    return "\n".join(lines) + "\n"


def wired_crossbar(
    g, v, r_source=100.0, r_tia=10.0, r_row=13.8, r_col=13.8,
    c_row=None, c_col=None, pwl_rows=(),
):
    """Crossbar with explicit uniform wire chains (one node per (i,j))."""
    m, n = g.shape
    lines = ["* wired crossbar"]
    for i in range(m):
        if i in pwl_rows:
            lines.append(f"Vdrive{i} d{i} 0 PWL(0 0 1e-09 {v[i]} 2e-08 {v[i]})")
        else:
            lines.append(f"Vdrive{i} d{i} 0 DC {v[i]}")
        lines.append(f"Rsrc{i} d{i} r{i}_0 {r_source}")
        for j in range(1, n):
            lines.append(f"Rrw{i}_{j} r{i}_{j - 1} r{i}_{j} {r_row}")
    for i in range(m):
        for j in range(n):
            if g[i, j] > 0:
                lines.append(f"Rdev{i}_{j} r{i}_{j} c{i}_{j} {1.0 / g[i, j]}")
            if c_row is not None and c_row[i, j] > 0:
                lines.append(f"Crow{i}_{j} r{i}_{j} 0 {c_row[i, j]}")
            if c_col is not None and c_col[i, j] > 0:
                lines.append(f"Ccol{i}_{j} c{i}_{j} 0 {c_col[i, j]}")
    for j in range(n):
        for i in range(1, m):
            lines.append(f"Rcw{i}_{j} c{i - 1}_{j} c{i}_{j} {r_col}")
        lines.append(f"Rtia{j} c{m - 1}_{j} 0 {r_tia}")
    return "\n".join(lines) + "\n"


def demo_g(m, n, seed=0):
    rng = np.random.default_rng(seed)
    r = rng.uniform(1.0 / MRAM.g_on, 1.0 / MRAM.g_off, size=(m, n))
    return 1.0 / r


# ---------------------------------------------------------------------------
# flatten
# ---------------------------------------------------------------------------


def test_flatten_inlines_instances():
    circ = parse_netlist(
        "* t\n.SUBCKT cell a b\nRx a mid 1000\nRy mid b 2000\n.ENDS\n"
        "Xc1 in GND cell\nV1 in 0 DC 1\n"
    )
    flat = flatten(circ)
    names = {c.name for c in flat.cards if hasattr(c, "name")}
    assert "Rx@Xc1" in names and "Ry@Xc1" in names
    rx = next(c for c in flat.cards if getattr(c, "name", "") == "Rx@Xc1")
    assert rx.n1 == "in" and rx.n2 == "Xc1.mid"  # internal node scoped
    ry = next(c for c in flat.cards if getattr(c, "name", "") == "Ry@Xc1")
    # Outer connection names pass through verbatim; ground aliases are
    # resolved at solve time (see test_solve_dc_ground_aliases).
    assert ry.n2 == "GND"
    assert not flat.subckts


def test_flatten_nested_instances():
    circ = parse_netlist(
        "* t\n.SUBCKT inner p\nRi p 0 1000\n.ENDS\n"
        ".SUBCKT outer q\nXo q inner\n.ENDS\n"
        "Xtop n1 outer\n"
    )
    flat = flatten(circ)
    (ri,) = [c for c in flat.cards if getattr(c, "name", "").startswith("Ri")]
    assert ri.name == "Ri@Xtop.Xo" and ri.n1 == "n1"


def test_flatten_errors():
    from repro.spice import ParseError

    with pytest.raises(ParseError, match="undefined subckt"):
        flatten(parse_netlist("* t\nXa n1 n2 nosuch\n"))
    with pytest.raises(ParseError, match="nodes for"):
        flatten(
            parse_netlist(
                "* t\n.SUBCKT one p\nR1 p 0 1\n.ENDS\nXa n1 n2 one\n"
            )
        )
    with pytest.raises(UnsupportedElementError, match="behavioural"):
        flatten(
            parse_netlist(
                "* t\n.SUBCKT act p q\nE1 q 0 VALUE={v(p)*2}\n.ENDS\n"
                "Xa a b act\n"
            )
        )


# ---------------------------------------------------------------------------
# solve_dc — the generic linear oracle
# ---------------------------------------------------------------------------


def test_solve_dc_divider():
    op = solve_dc(
        parse_netlist("* t\nV1 in 0 DC 1\nR1 in mid 1000\nR2 mid 0 3000\n")
    )
    assert op.voltages["mid"] == pytest.approx(0.75, rel=1e-12)
    assert op.voltages["in"] == pytest.approx(1.0)
    assert op.voltages["0"] == 0.0
    # Branch current convention: + terminal through the source. The
    # source supplies 0.25 mA, so the MNA branch current is negative.
    assert op.currents["V1"] == pytest.approx(-0.25e-3, rel=1e-9)


def test_solve_dc_isource_and_pwl():
    op = solve_dc(
        parse_netlist(
            "* t\nI1 a 0 DC 1m\nR1 a 0 1000\n"
            "V2 b 0 PWL(0 0 1e-09 0.5)\nR2 b 0 500\n"
        )
    )
    # I flows a -> 0 through the source: it is pulled out of node a.
    assert op.voltages["a"] == pytest.approx(-1.0, rel=1e-9)
    assert op.voltages["b"] == pytest.approx(0.5)  # PWL settles at 0.5


def test_solve_dc_caps_open():
    op = solve_dc(
        parse_netlist(
            "* t\nV1 in 0 DC 1\nR1 in mid 1000\nR2 mid 0 1000\n"
            "Cl mid 0 1e-12\n"
        )
    )
    assert op.voltages["mid"] == pytest.approx(0.5, rel=1e-12)


def test_solve_dc_flattens_subckts():
    op = solve_dc(
        parse_netlist(
            "* t\n.SUBCKT div a b\nR1 a m 1000\nR2 m b 1000\n.ENDS\n"
            "Xd in 0 div\nV1 in 0 DC 2\n"
        )
    )
    assert op.voltages["Xd.m"] == pytest.approx(1.0, rel=1e-12)


def test_solve_dc_rejections():
    with pytest.raises(UnsupportedElementError, match="behavioural"):
        solve_dc(parse_netlist("* t\nE1 o 0 VALUE={1+1}\nR1 o 0 1\n"))
    with pytest.raises(UnsupportedElementError, match="non-positive"):
        solve_dc(parse_netlist("* t\nV1 a 0 DC 1\nR1 a 0 0\n"))
    with pytest.raises(UnsupportedElementError, match="singular"):
        solve_dc(parse_netlist("* t\nR1 a b 1000\nR2 b a 1000\n"))


def test_solve_dc_ground_aliases():
    op = solve_dc(
        parse_netlist("* t\nV1 in gnd DC 1\nR1 in mid 1000\nR2 mid VSS! 1000\n")
    )
    assert op.voltages["mid"] == pytest.approx(0.5, rel=1e-12)


# ---------------------------------------------------------------------------
# lower_crossbar — structural recognition
# ---------------------------------------------------------------------------


def test_lower_ideal_crossbar_recovers_structure():
    g = demo_g(3, 2)
    v = np.array([0.3, 0.5, 0.8])
    xb = lower_crossbar(parse_netlist(ideal_crossbar(g, v)))
    assert xb.shape == (3, 2)
    np.testing.assert_allclose(xb.g, g, rtol=1e-9)
    np.testing.assert_allclose(xb.v_in, v, rtol=1e-12)
    assert xb.r_source == 100.0 and xb.r_tia == 10.0
    assert xb.r_row == xb.r_col == R_WIRE_EPS


def test_lower_ideal_crossbar_matches_generic_solve():
    """Acceptance: dense MNA on the lowered structure == the generic
    nodal solve of the netlist, node by node (x64; the eps wires bound
    the agreement at ~1e-7 relative)."""
    g = demo_g(3, 2, seed=1)
    v = np.array([0.3, 0.5, 0.8])
    circ = parse_netlist(ideal_crossbar(g, v))
    xb = lower_crossbar(circ)
    op = solve_dc(circ)
    with jax.enable_x64(True):
        got = xb.node_voltages(xb.solve_dense())
    for node, want in op.voltages.items():
        if node in got:
            assert got[node] == pytest.approx(want, rel=1e-5, abs=1e-9), node


def test_lower_wired_crossbar_matches_generic_solve():
    """Wire-grid form: float64 agreement is essentially exact (1e-9)."""
    g = demo_g(3, 4, seed=2)
    v = np.array([0.1, 0.7, 0.4])
    circ = parse_netlist(wired_crossbar(g, v))
    xb = lower_crossbar(circ)
    np.testing.assert_allclose(xb.g, g, rtol=1e-9)
    assert xb.r_row == 13.8 and xb.r_col == 13.8
    op = solve_dc(circ)
    with jax.enable_x64(True):
        got = xb.node_voltages(xb.solve_dense())
    for node, want in got.items():
        assert want == pytest.approx(op.voltages[node], rel=1e-9, abs=1e-15)


def test_lower_wired_crossbar_backend_agreement():
    """The production iterative solve on the lowered tile matches the
    dense oracle at float32 tolerances."""
    g = demo_g(4, 4, seed=3)
    v = np.array([0.2, 0.8, 0.5, 0.3])
    xb = lower_crossbar(parse_netlist(wired_crossbar(g, v)))
    dense = xb.solve_dense(gs_iters=96)
    fast = xb.solve(gs_iters=96)
    np.testing.assert_allclose(
        np.asarray(fast.i_out), np.asarray(dense.i_out), rtol=1e-3
    )


def test_lower_crossbar_ammeter_merge():
    """0 V sources in series (SPICE ammeters) are merged as shorts."""
    g = demo_g(2, 2, seed=4)
    v = np.array([0.4, 0.6])
    text = wired_crossbar(g, v)
    # Splice an ammeter between the column foot and its TIA.
    text = text.replace(
        "Rtia0 c1_0 0 10.0", "Vsense0 c1_0 foot0 DC 0\nRtia0 foot0 0 10.0"
    )
    xb = lower_crossbar(parse_netlist(text))
    np.testing.assert_allclose(xb.g, g, rtol=1e-9)


def test_lower_crossbar_pwl_and_reversed_driver():
    g = demo_g(2, 2, seed=5)
    v = np.array([0.25, 0.5])
    text = wired_crossbar(g, v, pwl_rows=(0,))
    # Reverse row 1's driver: V 0 node DC -v is the same drive.
    text = text.replace("Vdrive1 d1 0 DC 0.5", "Vdrive1 0 d1 DC -0.5")
    xb = lower_crossbar(parse_netlist(text))
    np.testing.assert_allclose(xb.v_in, v, rtol=1e-12)
    assert 0 in xb.pwl and xb.pwl[0][-1] == (2e-8, 0.25)
    assert 1 not in xb.pwl


def test_lower_crossbar_caps():
    g = demo_g(2, 3, seed=6)
    v = np.array([0.3, 0.6])
    c_row = np.full_like(g, 1e-15)
    c_col = np.full_like(g, 2e-15)
    xb = lower_crossbar(
        parse_netlist(wired_crossbar(g, v, c_row=c_row, c_col=c_col))
    )
    np.testing.assert_allclose(xb.c_row, c_row)
    np.testing.assert_allclose(xb.c_col, c_col)


def test_lower_crossbar_floating_cap_rejected():
    g = demo_g(2, 2, seed=7)
    text = wired_crossbar(g, np.array([0.1, 0.2]))
    text += "Cfloat r0_0 c1_1 1e-15\n"
    with pytest.raises(NonCrossbarError, match="floats between"):
        lower_crossbar(parse_netlist(text))


@pytest.mark.parametrize(
    "mutate,msg",
    [
        (lambda t: t + "I1 r0_0 0 DC 1m\n", "current source"),
        (lambda t: t + "Vf r0_0 c1_1 DC 0.2\n", "floats between"),
        (lambda t: t + "Vdup d0 0 DC 0.9\n", "drive the same node"),
        (
            lambda t: t.replace("Rtia0 c1_0 0 10.0", "Rhalf c1_0 0 10.0\n"
                                "Rtia0 c1_0 0 10.0"),
            "share a column foot",
        ),
        (
            lambda t: t.replace("Rtia1 c1_1 0 10.0", "Rtia1 c1_1 0 22.0"),
            "must be uniform",
        ),
        (lambda t: t + "Rextra d0 r0_1 50\n", "resistor connections"),
    ],
)
def test_lower_crossbar_diagnostics(mutate, msg):
    g = demo_g(2, 2, seed=8)
    text = mutate(wired_crossbar(g, np.array([0.3, 0.4])))
    with pytest.raises(NonCrossbarError, match=msg):
        lower_crossbar(parse_netlist(text))


def test_lower_crossbar_no_tia():
    text = "* t\nV0 d0 0 DC 1\nRs d0 row0 100\nRd row0 col0 10000\n"
    with pytest.raises(NonCrossbarError, match="TIA"):
        lower_crossbar(parse_netlist(text))


def test_lower_crossbar_duplicate_device():
    g = demo_g(2, 2, seed=9)
    text = ideal_crossbar(g, np.array([0.5, 0.5]))
    text += "Rdup row0 col1 5000\n"
    with pytest.raises(NonCrossbarError, match="two devices bridge"):
        lower_crossbar(parse_netlist(text))


def test_lower_crossbar_behavioral_rejected():
    text = "* t\nE1 out 0 VALUE={v(a)}\nV1 a 0 DC 1\nR1 a 0 100\n"
    with pytest.raises(NonCrossbarError, match="behavioural"):
        lower_crossbar(parse_netlist(text))


# ---------------------------------------------------------------------------
# lower_network — generated netlists back to engine structures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gen_net():
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    params = [
        (jax.random.normal(k1, (6, 4)), jnp.zeros((4,))),
        (jax.random.normal(k2, (4, 3)), jnp.zeros((3,))),
    ]
    cfg = IMACConfig(tech="MRAM", array_rows=4, array_cols=4)
    mapped = map_network(params, MRAM, v_unit=cfg.vdd)
    plans = build_plans([6, 4, 3], cfg)
    return cfg, mapped, plans


def test_lower_network_recovers_conductances(gen_net):
    cfg, mapped, plans = gen_net
    sample = np.linspace(0.0, 1.0, 6)
    files = map_imac(mapped, plans, cfg, sample=sample)
    net = lower_network(files)
    assert net.topology == [6, 4, 3]
    assert net.v_unit == pytest.approx(cfg.vdd)
    for la, mp in zip(net.layers, mapped):
        np.testing.assert_allclose(la.g_pos, np.asarray(mp.g_pos), rtol=1e-5)
        np.testing.assert_allclose(la.g_neg, np.asarray(mp.g_neg), rtol=1e-5)
    np.testing.assert_allclose(net.sample, sample, atol=2e-6)
    assert net.tran is not None and not net.has_pwl


def test_lower_network_to_mapped_and_config(gen_net):
    cfg, mapped, plans = gen_net
    files = map_imac(mapped, plans, cfg)
    net = lower_network(files)
    for got, want in zip(net.to_mapped(), mapped):
        assert got.sense_r == pytest.approx(want.sense_r, rel=1e-4)
        assert got.k == pytest.approx(want.k, rel=1e-4)
    cfg2 = net.to_config()
    assert (cfg2.array_rows, cfg2.array_cols) == (4, 4)
    assert cfg2.hp == [la.plan.hp for la in net.layers]
    assert cfg2.vp == [la.plan.vp for la in net.layers]
    assert cfg2.r_source == pytest.approx(cfg.r_source)
    assert cfg2.r_tia == pytest.approx(cfg.r_tia)
    assert cfg2.interconnect.r_segment == pytest.approx(
        cfg.interconnect.r_segment, rel=1e-4
    )
    assert cfg2.transient is None  # DC deck: .TRAN header but no PWL


def test_lower_network_transient_roundtrip(gen_net):
    from repro.transient.spec import TransientSpec

    cfg, mapped, plans = gen_net
    spec = TransientSpec(t_stop=2e-9, n_steps=8, method="trap")
    files = map_imac(mapped, plans, cfg, transient=spec)
    net = lower_network(files)
    assert net.has_pwl and net.method == "trap"
    got = net.to_config().transient
    assert got is not None
    assert got.t_stop == pytest.approx(spec.t_stop, rel=1e-5)
    assert got.n_steps == spec.n_steps
    assert got.method == "trap"
    assert got.t_rise == pytest.approx(spec.resolved_t_rise(), rel=1e-5)


def test_lower_network_missing_bias(gen_net):
    cfg, mapped, plans = gen_net
    files = dict(map_imac(mapped, plans, cfg))
    files["imac_main.sp"] = "\n".join(
        ln
        for ln in files["imac_main.sp"].splitlines()
        if not ln.startswith("Vbias_")
    ) + "\n"
    with pytest.raises(NonCrossbarError, match="Vbias"):
        lower_network(files)


def test_lower_dispatch(gen_net):
    cfg, mapped, plans = gen_net
    from repro.spice import LoweredCrossbar, LoweredNetwork

    assert isinstance(lower(map_imac(mapped, plans, cfg)), LoweredNetwork)
    g = demo_g(2, 2)
    flat = {"tile.sp": wired_crossbar(g, np.array([0.2, 0.4]))}
    assert isinstance(lower(flat), LoweredCrossbar)


def test_evaluate_netlist_matches_direct_eval(gen_net):
    from repro.core.evaluate import evaluate_batch, evaluate_netlist

    cfg, mapped, plans = gen_net
    files = map_imac(mapped, plans, cfg)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.uniform(0.0, 1.0, size=(16, 6)), dtype=jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, size=(16,)))
    result, net = evaluate_netlist(files, x, y)
    params = [
        (jnp.asarray(w), jnp.asarray(b)) for w, b in net.to_params()
    ]
    want = evaluate_batch(
        params, x, y, [net.to_config()], mapped=[net.to_mapped()]
    )[0]
    assert result.accuracy == pytest.approx(want.accuracy, abs=1e-9)
    assert result.avg_power == pytest.approx(want.avg_power, rel=1e-6)
    assert result.latency_source == "analytic"


# ---------------------------------------------------------------------------
# solve_dense_mna with companion stamps (transient oracle support)
# ---------------------------------------------------------------------------


def test_dense_mna_accepts_stamps():
    g = jnp.asarray(demo_g(3, 3, seed=12))
    v = jnp.asarray(np.array([0.2, 0.5, 0.8]), dtype=jnp.float32)
    cp = CircuitParams(gs_iters=96, tol=0.0)
    shunt = jnp.full((3, 3), 1e-4)
    inj = jnp.full((3, 3), 1e-6)
    stamps = Stamps(
        g_shunt_row=shunt, g_shunt_col=shunt, i_inj_row=inj, i_inj_col=inj
    )
    dense = solve_dense_mna(g, v, cp, stamps=stamps)
    fast = solve_crossbar(g, v, cp, stamps=stamps)
    np.testing.assert_allclose(
        np.asarray(fast.vc), np.asarray(dense.vc), rtol=1e-3, atol=1e-6
    )
    # The stamps must actually perturb the solution.
    plain = solve_dense_mna(g, v, cp)
    assert not np.allclose(np.asarray(plain.vc), np.asarray(dense.vc))
