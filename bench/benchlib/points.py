"""Design points of a configuration file, as the reference and the program see them."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Point:
    name: str           # "<partitioning>/<technology>"
    tech: str
    partitioning: dict  # {"hp": [...], "vp": [...]} or {"array_rows", "array_cols"}
    group: str          # partitioning name: one structure per partitioning


def design_points(cfg: dict) -> "list[Point]":
    """Every partitioning x every swept technology, in file order."""
    return [
        Point(f"{part['name']}/{tech}", tech, part, part["name"])
        for part in cfg["partitionings"]
        for tech in cfg["technologies_swept"]
    ]


def program_config(cfg: dict, point: Point, *, parasitics: bool, dtype=None):
    """The simulator's `IMACConfig` for one point.

    Only what the configuration file and the traffic name are set; solver
    settings stay at the program's defaults, which the file records.
    `dtype` is for the lower-precision control alone.
    """
    from repro.core.imac import IMACConfig

    part = point.partitioning
    kw = dict(
        tech=point.tech,
        neuron=cfg["activation"],
        vdd=cfg["vdd"],
        vss=cfg["vss"],
        t_sampling=cfg["t_sampling_s"],
        parasitics=parasitics,
    )
    if "hp" in part:
        kw.update(hp=tuple(part["hp"]), vp=tuple(part["vp"]))
    else:
        kw.update(array_rows=part["array_rows"], array_cols=part["array_cols"])
    if dtype is not None:
        kw["dtype"] = dtype
    return IMACConfig(**kw)
