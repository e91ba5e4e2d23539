"""True speed functions of one Google cell, machine by machine.

The configuration's ``machines`` table lists the cell's machine classes as
``[count, platform, cpu, memory]``, capacities normalized to the largest
machine (Reiss et al., SoCC 2012, Table 1, for the 2011 cluster-usage
trace).  Every machine becomes one processor whose speed function has the
shape of ``benchmarks/partition_scale.py::make_fleet_bank`` (copied here so
that a change to it cannot move this yardstick):

* a plateau of ``cpu * plateau_units_per_s`` units/s;
* a cache boost below about 500 units, ``plateau * (1 + 0.4 exp(-x / 500))``;
* paging past a knee of ``memory * knee_units`` units, where the speed
  falls as ``plateau / (1 + 2 (x - knee) / knee)``.

The trace times no machine, so machines of one class differ only by a
uniform spread of ``+-class_spread`` on plateau and knee, drawn from the
truth seed, which also orders the machines.
"""

from __future__ import annotations

import numpy as np


def classes(config: dict):
    """``(cpu, memory)``, one entry per machine, in table order."""
    rows = config["machines"]
    counts = [int(r[0]) for r in rows]
    cpu = np.repeat([float(r[2]) for r in rows], counts)
    mem = np.repeat([float(r[3]) for r in rows], counts)
    return cpu, mem


def build(config: dict, seed: int):
    """``(xs, ss)``, each ``[p, 6]``: six (size, speed) knots per machine,
    linear between them and constant outside."""
    cpu, mem = classes(config)
    p = cpu.size
    rng = np.random.default_rng(seed)
    order = rng.permutation(p)
    cpu, mem = cpu[order], mem[order]
    spread = float(config["class_spread"])
    plateau = (cpu * float(config["plateau_units_per_s"])
               * rng.uniform(1.0 - spread, 1.0 + spread, p))[:, None]
    knee = (mem * float(config["knee_units"]) * rng.uniform(1.0 - spread, 1.0 + spread, p))[:, None]
    xs = np.exp(
        np.linspace(0.0, 1.0, 6)[None, :] * (np.log(8.0 * knee) - np.log(16.0))
        + np.log(16.0)
    )
    ss = np.where(
        xs <= knee,
        plateau * (1.0 + 0.4 * np.exp(-xs / 500.0)),
        plateau / (1.0 + 2.0 * (xs - knee) / knee),
    )
    return xs, ss
