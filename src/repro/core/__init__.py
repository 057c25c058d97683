"""IMAC-Sim-JAX core: the paper's contribution as composable JAX modules.

Public API:
  devices.DeviceTech / MRAM / RRAM / CBRAM / PCM
  interconnect.Interconnect
  mapping.map_wb / map_network          (Module 2: mapWB)
  partition.auto_partition / plan_partition / tile_matrix
  solver.solve_crossbar / solve_dense_mna (the "SPICE engine")
  solver.Stamps / SolveOptions           (stamp pytree + backend choice)
  backends.register_backend / available_backends / get_backend
  neurons.NeuronModel
  imac.IMACConfig / IMACNetwork / imac_linear (Modules 3-4)
  netlist.map_layer / map_imac          (SPICE netlist generation)
  evaluate.test_imac / evaluate_batch / evaluate_netlist / sweep
                                        (Module 1: testIMAC)
  moe.MoESpec / route; evaluate.map_moe / evaluate_moe
                                        (one chip's share of an MoE FFN
                                        layer through the crossbar solve)
"""
from repro.core.devices import (
    CBRAM,
    MRAM,
    PCM,
    RRAM,
    TECHNOLOGIES,
    DeviceTech,
    custom_tech,
    get_tech,
)
from repro.core.evaluate import (
    IMACResult,
    MoEResult,
    evaluate_batch,
    evaluate_moe,
    evaluate_netlist,
    map_moe,
    structure_key,
    sweep,
    test_imac,
)
from repro.core.imac import (
    IMACConfig,
    IMACNetwork,
    TransientStats,
    imac_linear,
    linear_forward,
)
from repro.core.interconnect import DEFAULT_INTERCONNECT, Interconnect
from repro.core.moe import MoESpec
from repro.core.mapping import MappedLayer, map_network, map_wb
from repro.core.netlist import (
    map_imac,
    map_layer,
    netlist_stats,
    parse_transient_directives,
)
from repro.core.neurons import NeuronModel, get_neuron
from repro.core.partition import PartitionPlan, auto_partition, plan_partition
from repro.core.backends import (
    SolverBackend,
    available_backends,
    default_backend_name,
    get_backend,
    register_backend,
)
from repro.core.solver import (
    CircuitParams,
    SolveOptions,
    Stamps,
    crossbar_power,
    solve_crossbar,
    solve_dense_mna,
    solve_ideal,
)

__all__ = [
    "CBRAM",
    "CircuitParams",
    "DEFAULT_INTERCONNECT",
    "DeviceTech",
    "IMACConfig",
    "IMACNetwork",
    "IMACResult",
    "Interconnect",
    "MRAM",
    "MappedLayer",
    "MoEResult",
    "MoESpec",
    "NeuronModel",
    "PCM",
    "PartitionPlan",
    "RRAM",
    "SolveOptions",
    "SolverBackend",
    "Stamps",
    "TECHNOLOGIES",
    "auto_partition",
    "available_backends",
    "crossbar_power",
    "custom_tech",
    "default_backend_name",
    "evaluate_batch",
    "evaluate_moe",
    "evaluate_netlist",
    "get_backend",
    "get_neuron",
    "get_tech",
    "imac_linear",
    "linear_forward",
    "map_imac",
    "map_layer",
    "map_moe",
    "map_network",
    "map_wb",
    "netlist_stats",
    "parse_transient_directives",
    "plan_partition",
    "register_backend",
    "TransientStats",
    "solve_crossbar",
    "solve_dense_mna",
    "solve_ideal",
    "structure_key",
    "sweep",
    "test_imac",
]
