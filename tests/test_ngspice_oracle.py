"""ngspice differential-oracle harness (repro.spice.oracle).

The rawfile parser and deck instrumentation are unit-tested with canned
strings everywhere. The live differential tests — generated and
hand-built netlists run through a real `ngspice -b`, DC node voltages
and transient waveforms compared against the in-repo backends — require
the binary and skip cleanly when it is absent (CI's optional oracle job
apt-installs it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_spice_lower import demo_g, wired_crossbar

from repro.core.solver import SolveOptions, solve_dense_mna
from repro.spice import lower_crossbar, lower_network, parse_netlist, solve_dc
from repro.spice.oracle import (
    NgspiceError,
    NgspiceResult,
    _instrument,
    find_ngspice,
    parse_raw,
    run_ngspice,
)

requires_ngspice = pytest.mark.skipif(
    find_ngspice() is None, reason="ngspice binary not installed"
)

BACKENDS = ("scan", "pallas", "fused")


def _opts(backend):
    return SolveOptions(backend=backend, interpret=True)


# ---------------------------------------------------------------------------
# Rawfile parsing (always runs).
# ---------------------------------------------------------------------------

OP_RAW = """Title: divider
Date: Thu Aug  7 12:00:00 2026
Plotname: Operating Point
Flags: real
No. Variables: 3
No. Points: 1
Variables:
\t0\tv(a)\tvoltage
\t1\tv(b)\tvoltage
\t2\ti(v1)\tcurrent
Values:
0\t7.5e-01
\t5e-01
\t-2.5e-04
"""

TRAN_RAW = """Title: ramp
Plotname: Transient Analysis
Flags: real
No. Variables: 2
No. Points: 3
Variables:
\t0\ttime\ttime
\t1\tv(out)\tvoltage
Values:
0\t0.0
\t0.0
1\t1e-09
\t4e-01
2\t2e-09\t5e-01
"""


def test_parse_raw_op():
    (plot,) = parse_raw(OP_RAW)
    assert plot.name == "Operating Point"
    assert plot.variables == ("v(a)", "v(b)", "i(v1)")
    assert plot.values.shape == (1, 3)
    assert plot.voltage("a") == pytest.approx(0.75)
    assert plot.voltage("B") == pytest.approx(0.5)  # case-insensitive
    assert plot.signal("v1")[0] == pytest.approx(-2.5e-4)
    with pytest.raises(KeyError, match="nosuch"):
        plot.signal("nosuch")


def test_parse_raw_tran_and_multiplot():
    plots = parse_raw(OP_RAW + TRAN_RAW)
    assert [p.name for p in plots] == ["Operating Point", "Transient Analysis"]
    tran = plots[1]
    np.testing.assert_allclose(tran.time(), [0.0, 1e-9, 2e-9])
    np.testing.assert_allclose(tran.signal("out"), [0.0, 0.4, 0.5])


def test_parse_raw_complex_values():
    text = OP_RAW.replace("Flags: real", "Flags: complex")
    text = text.replace("7.5e-01", "7.5e-01,0.0")
    (plot,) = parse_raw(text)
    assert plot.voltage("a") == pytest.approx(0.75)  # real part kept


@pytest.mark.parametrize(
    "mutate,msg",
    [
        (lambda t: t.replace("No. Variables: 3\n", ""), "header missing"),
        (lambda t: t.replace("\t5e-01\n", ""), "value tokens"),
        (lambda t: t.replace("Values:\n0\t", "Values:\n9\t"), "point index"),
        (lambda t: "just some text\n", "no plots"),
    ],
)
def test_parse_raw_errors(mutate, msg):
    with pytest.raises(NgspiceError, match=msg):
        parse_raw(mutate(OP_RAW))


def test_parse_raw_variable_count_mismatch():
    bad = OP_RAW.replace("\t2\ti(v1)\tcurrent\n", "")
    with pytest.raises(NgspiceError, match="header says"):
        parse_raw(bad)


def test_result_plot_selection():
    plots = tuple(parse_raw(OP_RAW + TRAN_RAW))
    res = NgspiceResult(plots=plots, log="")
    assert res.op().name == "Operating Point"
    assert res.tran().name == "Transient Analysis"
    with pytest.raises(KeyError, match="no 'noise' plot"):
        res.plot("noise")


def test_instrument_splices_before_end():
    deck = "* t\nR1 a 0 1\n.END\n"
    out = _instrument(deck, "out.raw")
    assert out.index(".control") < out.upper().index(".END")
    assert "write out.raw all" in out
    # Without .end the control block is appended and .end added.
    out2 = _instrument("* t\nR1 a 0 1\n", "x.raw")
    assert out2.rstrip().endswith(".end")


def test_run_ngspice_missing_binary(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_NGSPICE", str(tmp_path / "nonexistent"))
    with pytest.raises(NgspiceError, match="not found"):
        run_ngspice({"a.sp": "* t\n.end\n"})


def test_run_ngspice_main_inference_error():
    with pytest.raises(NgspiceError, match="cannot infer"):
        run_ngspice(
            {"a.sp": "* a\n", "b.sp": "* b\n"}, ngspice="/bin/true"
        )


# ---------------------------------------------------------------------------
# Live differential tests (need the binary).
# ---------------------------------------------------------------------------


@requires_ngspice
def test_ngspice_dc_crossbar_vs_backends():
    """DC node voltages: ngspice vs the generic nodal oracle (1e-6),
    the crossbar dense MNA (1e-5) and every iterative backend (1e-3)."""
    g = demo_g(4, 3, seed=31)
    v = np.array([0.2, 0.7, 0.45, 0.9])
    text = wired_crossbar(g, v) + ".op\n.end\n"
    circ = parse_netlist(text)
    res = run_ngspice({"tile.sp": text})
    op = res.op()

    ours = solve_dc(circ)
    for node, want in ours.voltages.items():
        if node == "0":
            continue
        assert op.voltage(node) == pytest.approx(want, rel=1e-6, abs=1e-12), node

    xb = lower_crossbar(circ)
    with jax.enable_x64(True):
        dense = xb.node_voltages(xb.solve_dense())
    for node, want in dense.items():
        assert op.voltage(node) == pytest.approx(want, rel=1e-5, abs=1e-9), node

    # TIA currents through the production backends: i_out = v_foot/r_tia.
    i_ng = np.array(
        [op.voltage(f"c{g.shape[0] - 1}_{j}") / xb.r_tia for j in range(3)]
    )
    for backend in BACKENDS:
        sol = xb.solve(options=_opts(backend), gs_iters=200)
        np.testing.assert_allclose(
            np.asarray(sol.i_out), i_ng, rtol=1e-3, atol=1e-9,
            err_msg=f"backend {backend}",
        )


@requires_ngspice
@pytest.mark.parametrize("backend", BACKENDS)
def test_ngspice_transient_waveform(backend):
    """Transient column-foot waveforms: ngspice vs the implicit
    integrator running on each backend."""
    from repro.transient.integrator import integrate_tiles
    from repro.transient.spec import TransientSpec

    m, n = 3, 2
    g = demo_g(m, n, seed=32)
    v = np.array([0.8, 0.4, 0.6])
    c_seg, c_driver, c_tia = 5e-16, 1e-15, 2e-15
    c_row = np.full((m, n), c_seg)
    c_row[:, 0] += c_driver
    c_col = np.full((m, n), c_seg)
    c_col[m - 1, :] += c_tia
    text = wired_crossbar(
        g, v, c_row=c_row, c_col=c_col, pwl_rows=tuple(range(m))
    )
    text += ".tran 2e-10 2e-08\n.end\n"

    res = run_ngspice({"tile.sp": text})
    tran = res.tran()
    t_ng = tran.time()

    circ = parse_netlist(text)
    xb = lower_crossbar(circ)
    assert set(xb.pwl) == set(range(m))
    spec = TransientSpec(t_stop=2e-8, n_steps=50, method="trap", t_rise=1e-9)
    dt = spec.t_stop / spec.n_steps
    out = integrate_tiles(
        jnp.asarray(xb.g, dtype=jnp.float32),
        jnp.asarray(xb.v_in, dtype=jnp.float32),
        xb.circuit_params(gs_iters=96),
        spec,
        dt,
        c_row=jnp.asarray(xb.c_row, dtype=jnp.float32),
        c_col=jnp.asarray(xb.c_col, dtype=jnp.float32),
        t_rise=1e-9,
        solve_options=_opts(backend),
        record=True,
    )
    wave = np.asarray(out.waveform)  # (steps, N) column-foot voltages
    t_ours = dt * np.arange(1, spec.n_steps + 1)
    scale = float(np.max(np.abs(wave))) or 1.0
    for j in range(n):
        foot = f"c{m - 1}_{j}"
        v_ng = np.interp(t_ours, t_ng, tran.signal(foot))
        # Same circuit, same method (trap), different steppers: agree to
        # a few percent of the waveform scale throughout the ramp and
        # tightly at the settled tail.
        np.testing.assert_allclose(
            wave[:, j], v_ng, atol=0.05 * scale, rtol=0.05,
            err_msg=f"waveform mismatch on {foot} ({backend})",
        )
        assert wave[-1, j] == pytest.approx(v_ng[-1], rel=2e-2, abs=1e-5)


@requires_ngspice
def test_ngspice_accepts_generated_netlist():
    """A full map_imac deck (subckts, PWL-free DC drives, behavioural
    neurons) runs in ngspice, and the neuron output voltages match the
    lowered network solved by the dense MNA oracle."""
    from repro.core.devices import MRAM
    from repro.core.imac import IMACConfig, build_plans
    from repro.core.mapping import map_network
    from repro.core.netlist import map_imac
    from repro.core.partition import tile_matrix

    key = jax.random.PRNGKey(33)
    params = [(jax.random.normal(key, (4, 3)), jnp.zeros((3,)))]
    cfg = IMACConfig(tech="MRAM", array_rows=8, array_cols=8)
    mapped = map_network(params, MRAM, v_unit=cfg.vdd)
    plans = build_plans([4, 3], cfg)
    sample = np.array([0.15, 0.9, 0.35, 0.6])
    files = map_imac(mapped, plans, cfg, sample=sample)

    res = run_ngspice(files)
    op = res.op()

    net = lower_network(files)
    la = net.layers[0]
    assert la.plan.n_tiles == 1  # 5x3 fits one 8x8 tile
    gp = np.asarray(tile_matrix(jnp.asarray(la.g_pos), la.plan))[0]
    gn = np.asarray(tile_matrix(jnp.asarray(la.g_neg), la.plan))[0]
    v_in = np.concatenate([sample * net.v_unit, [net.v_unit]])
    cp = net.to_config().circuit_params(la.plan.rows, la.plan.cols)
    with jax.enable_x64(True):
        i_p = np.asarray(solve_dense_mna(jnp.asarray(gp), jnp.asarray(v_in), cp).i_out)
        i_n = np.asarray(solve_dense_mna(jnp.asarray(gn), jnp.asarray(v_in), cp).i_out)
    z = (i_p - i_n) * la.neuron.sense_scale
    nrn = la.neuron
    if nrn.kind == "sigmoid":
        v_pred = nrn.vdd / (1.0 + np.exp(-z / nrn.z_volt))
    elif nrn.kind == "tanh":
        v_pred = nrn.vdd * np.tanh(z / nrn.z_volt)
    elif nrn.kind == "relu":
        v_pred = np.maximum(0.0, z)
    else:
        v_pred = z
    for j in range(3):
        got = op.voltage(f"x1_{j}")
        assert np.isfinite(got)
        assert got == pytest.approx(v_pred[j], rel=1e-2, abs=5e-3), f"x1_{j}"
