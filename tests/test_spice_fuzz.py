"""Hypothesis fuzzing of the SPICE interchange (repro.spice).

Two properties, each with fixed-example twins that run even without
hypothesis installed:

  * round-trip stability — every netlist `map_imac` emits parses back
    to an equivalent `Circuit` (byte-stable re-emit, conductances and
    drives recovered), over random topologies/techs/samples;
  * parser-vs-dense-MNA agreement — a random wire-grid crossbar
    netlist, lowered structurally, solved by the crossbar dense MNA
    oracle, matches the generic nodal solve of the *parsed text* to
    1e-6 relative on every node voltage (float64).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypcompat import given, seed, settings, st
from test_spice_lower import wired_crossbar

from repro.core.devices import CBRAM, MRAM, PCM, RRAM
from repro.core.imac import IMACConfig, build_plans
from repro.core.mapping import map_network
from repro.core.netlist import map_imac, netlist_stats
from repro.spice import emit, lower_crossbar, lower_network, parse_netlist, solve_dc

TECHS = {"MRAM": MRAM, "RRAM": RRAM, "CBRAM": CBRAM, "PCM": PCM}


# ---------------------------------------------------------------------------
# Property 1: generated netlists round-trip.
# ---------------------------------------------------------------------------


def check_generated_roundtrip(topology, array_size, tech_name, seed_, transient):
    tech = TECHS[tech_name]
    cfg = IMACConfig(
        tech=tech_name, array_rows=array_size, array_cols=array_size
    )
    key = jax.random.PRNGKey(seed_)
    params = []
    for fan_in, fan_out in zip(topology, topology[1:]):
        key, k = jax.random.split(key)
        params.append(
            (jax.random.normal(k, (fan_in, fan_out)), jnp.zeros((fan_out,)))
        )
    mapped = map_network(params, tech, v_unit=cfg.vdd)
    plans = build_plans(topology, cfg)
    rng = np.random.default_rng(seed_)
    sample = rng.uniform(0.0, 1.0, size=topology[0])
    spec = None
    if transient:
        from repro.transient.spec import TransientSpec

        spec = TransientSpec(t_stop=2e-9, n_steps=8, method="trap")
    files = map_imac(mapped, plans, cfg, sample=sample, transient=spec)

    # Byte-stable: parse each file and re-emit.
    for name, text in files.items():
        assert emit(parse_netlist(text)) == text, f"{name} not byte-stable"
    # Structural stats survive the round trip.
    reemitted = {n: emit(parse_netlist(t)) for n, t in files.items()}
    assert netlist_stats(reemitted) == netlist_stats(files)

    # Equivalent Circuit: lowering recovers the mapping and the drives.
    net = lower_network(files)
    assert net.topology == list(topology)
    for la, mp in zip(net.layers, mapped):
        np.testing.assert_allclose(la.g_pos, np.asarray(mp.g_pos), rtol=1e-5)
        np.testing.assert_allclose(la.g_neg, np.asarray(mp.g_neg), rtol=1e-5)
    np.testing.assert_allclose(net.sample, sample, atol=2e-6)
    assert net.has_pwl == transient


@seed(2026)
@given(
    n_in=st.integers(min_value=2, max_value=6),
    hidden=st.integers(min_value=1, max_value=5),
    n_out=st.integers(min_value=1, max_value=4),
    array_size=st.integers(min_value=2, max_value=5),
    tech_name=st.sampled_from(sorted(TECHS)),
    seed_=st.integers(min_value=0, max_value=2**16),
    transient=st.booleans(),
)
@settings(max_examples=20)
def test_fuzz_generated_roundtrip(
    n_in, hidden, n_out, array_size, tech_name, seed_, transient
):
    check_generated_roundtrip(
        [n_in, hidden, n_out], array_size, tech_name, seed_, transient
    )


@pytest.mark.parametrize(
    "topology,array_size,tech_name,seed_,transient",
    [
        ([6, 4, 3], 4, "MRAM", 0, False),
        ([5, 3, 2], 3, "RRAM", 7, True),
        ([2, 5, 4], 2, "PCM", 123, False),
    ],
)
def test_generated_roundtrip_examples(
    topology, array_size, tech_name, seed_, transient
):
    check_generated_roundtrip(topology, array_size, tech_name, seed_, transient)


# ---------------------------------------------------------------------------
# Property 2 (acceptance criterion): lowered netlists match the dense
# MNA oracle to 1e-6 relative on node voltages.
# ---------------------------------------------------------------------------


def check_lowered_matches_dense_mna(
    m, n, seed_, r_source, r_tia, r_row, r_col
):
    rng = np.random.default_rng(seed_)
    g = 1.0 / rng.uniform(5e3, 3e5, size=(m, n))
    v = rng.uniform(0.0, 1.0, size=m)
    text = wired_crossbar(
        g, v, r_source=r_source, r_tia=r_tia, r_row=r_row, r_col=r_col
    )
    circ = parse_netlist(text)
    xb = lower_crossbar(circ)
    np.testing.assert_allclose(xb.g, g, rtol=1e-9)
    np.testing.assert_allclose(xb.v_in, v, rtol=1e-12)

    op = solve_dc(circ)  # generic nodal solve of the parsed text
    with jax.enable_x64(True):
        got = xb.node_voltages(xb.solve_dense())
    assert got, "no node voltages recovered"
    for node, want in got.items():
        assert want == pytest.approx(op.voltages[node], rel=1e-6, abs=1e-12), (
            f"node {node}: dense MNA {want} vs nodal oracle "
            f"{op.voltages[node]}"
        )


@seed(2027)
@given(
    m=st.integers(min_value=2, max_value=5),
    n=st.integers(min_value=2, max_value=5),
    seed_=st.integers(min_value=0, max_value=2**16),
    r_source=st.floats(min_value=10.0, max_value=500.0),
    r_tia=st.floats(min_value=1.0, max_value=50.0),
    r_row=st.floats(min_value=1.0, max_value=60.0),
    r_col=st.floats(min_value=1.0, max_value=60.0),
)
@settings(max_examples=25)
def test_fuzz_lowered_matches_dense_mna(
    m, n, seed_, r_source, r_tia, r_row, r_col
):
    check_lowered_matches_dense_mna(m, n, seed_, r_source, r_tia, r_row, r_col)


@pytest.mark.parametrize(
    "m,n,seed_,r_source,r_tia,r_row,r_col",
    [
        (2, 2, 0, 100.0, 10.0, 13.8, 13.8),
        (4, 3, 42, 250.0, 5.0, 2.0, 55.0),
        (5, 5, 9, 33.0, 47.0, 21.5, 1.25),
    ],
)
def test_lowered_matches_dense_mna_examples(
    m, n, seed_, r_source, r_tia, r_row, r_col
):
    check_lowered_matches_dense_mna(m, n, seed_, r_source, r_tia, r_row, r_col)


# ---------------------------------------------------------------------------
# Property 3: third-party text canonicalizes in one round trip.
# ---------------------------------------------------------------------------


def check_canonicalization(m, n, seed_, upper):
    rng = np.random.default_rng(seed_)
    g = 1.0 / rng.uniform(5e3, 3e5, size=(m, n))
    v = rng.uniform(0.0, 1.0, size=m)
    text = wired_crossbar(g, v)
    if upper:
        text = text.upper()
    once = emit(parse_netlist(text))
    assert emit(parse_netlist(once)) == once


@seed(2028)
@given(
    m=st.integers(min_value=1, max_value=4),
    n=st.integers(min_value=1, max_value=4),
    seed_=st.integers(min_value=0, max_value=2**16),
    upper=st.booleans(),
)
@settings(max_examples=25)
def test_fuzz_canonicalization(m, n, seed_, upper):
    check_canonicalization(m, n, seed_, upper)


def test_canonicalization_example():
    check_canonicalization(3, 3, 5, True)
