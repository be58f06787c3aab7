"""A cell, found by its name: `workloads/<name>.json` names a configuration
(`configs/<config>.json`), a traffic mix (`traffic/<traffic>.json`) and the
per-layer metrics it reads (`layer_metrics/<metric>.py`); the configuration
names its runner (`runners/<runner>.py`) and its plain reference
(`reference/<reference>.py`). There is no table of names in code, and
nothing here knows what a family's inputs or steps are: a new configuration,
mix, metric or family is a new file.

What every traffic mix has, whatever else its family's generator reads:

    ring          distinct batches, sent round-robin, one per step
    sync_every    the loop reads the loss every this many steps, as a
                  training loop that logs does
    trace_steps   steps in the traced window of a `--trace 1` run
    tiny          sizes for `--cpu-dry-run`
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        known = sorted(n[:-5] for n in os.listdir(os.path.join(HERE, kind))
                       if n.endswith(".json"))
        raise SystemExit(f"no {path}; {kind} here: {known}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    layout: dict            # mesh axes and what the mesh cannot say
    config: dict            # the configuration file, as it is run
    traffic: dict
    runner: ModuleType
    reference: ModuleType
    layer_metrics: dict     # name -> its reader module

    @property
    def mesh_shape(self) -> dict:
        return self.layout.get("mesh", {})


def load_traffic(name: str, tiny: bool = False) -> dict:
    traffic = _load("traffic", name)
    if tiny:
        traffic.update(traffic.get("tiny", {}))
    for key in ("ring", "sync_every", "trace_steps"):
        if not (isinstance(traffic.get(key), int) and traffic[key] > 0):
            raise ValueError(f"traffic {name!r}: {key} must be a positive "
                             f"integer, got {traffic.get(key)!r}")
    return traffic


def load_cell(name: str, tiny: bool = False) -> Cell:
    """`tiny` takes the files' own `tiny` sizes: the --cpu-dry-run cut."""
    cell = _load("workloads", name)
    config = _load("configs", cell["config"])
    if tiny:
        config.update(config.get("tiny", {}))
    layout = cell.get("layout") or {}
    chips = cell["chips"]
    if math.prod(layout.get("mesh", {}).values()) != chips:
        raise ValueError(f"cell {name!r}: mesh {layout.get('mesh')} does not "
                         f"hold {chips} chips")
    return Cell(
        name=name, chips=chips, layout=layout, config=config,
        traffic=load_traffic(cell["traffic"], tiny),
        runner=importlib.import_module(
            f"benchmarks.runners.{config['runner']}"),
        reference=importlib.import_module(
            f"benchmarks.reference.{config['reference']}"),
        layer_metrics={m: importlib.import_module(
            f"benchmarks.layer_metrics.{m}") for m in cell["layer_metrics"]})
