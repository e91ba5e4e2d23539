"""True speed functions of NERSC's Perlmutter, device by device.

The configuration's ``nodes`` table lists the node kinds as ``[count, kind,
sockets, cores_per_socket, dram_gb, gpus, gpu_memory_gb]``.  Processors
follow the functional performance model's treatment of multicore and
multi-GPU nodes (Zhong, Rychkov and Lastovetsky, IEEE Trans. Computers
2015): each GPU, driven by one dedicated core, is one processor, and the
remaining cores of a socket form one multicore processor.  Groups are
nodes.  The unit of work is one ``unit_block`` x ``unit_block`` float64 block
update of a blocked matrix multiplication; a processor holding ``x`` units
keeps ``x`` blocks resident.

* A GPU runs ``gpu_plateau_units_per_s`` on its plateau, with a ramp
  ``x / (x + gpu_ramp_units)`` below a few tens of units; past its memory
  in units (the knee) every further unit streams in and out over
  ``gpu_link_gb_per_s``.
* A CPU processor runs ``cpu_plateau_units_per_s * cores / 64``, with a
  cache boost ``1 + cpu_cache_boost * exp(-x / l3_units)`` while its blocks
  fit the socket's L3; past its share of the node's DRAM every further unit
  pages at ``cpu_page_gb_per_s``.

The time of ``x`` units is ``x / speed`` below the knee and the knee's time
plus the overflow's transfer above it.  Each processor's function is
tabulated at the knots ``knot_units`` (and half, one, one and a quarter and
two knees), linear in speed between them and constant outside, as the
paper's model takes it.  Devices of one kind differ by a uniform
``+-spread`` on the plateau, drawn from the truth seed, which also orders the
nodes; processors of a node are contiguous.
"""

from __future__ import annotations

import numpy as np


def processors(config: dict):
    """Per processor, in node order: ``(node, is_gpu, cores, knee_units)``."""
    unit_bytes = 8.0 * config["unit_block"] ** 2
    node, gpu, cores, knee = [], [], [], []
    n_node = 0
    for count, _kind, sockets, cps, dram_gb, gpus, gpu_gb in config["nodes"]:
        for _ in range(int(count)):
            host_cores = sockets * cps - gpus  # one core drives each GPU
            cpu_procs = int(sockets)
            for _ in range(int(gpus)):
                node.append(n_node)
                gpu.append(True)
                cores.append(1)
                knee.append(gpu_gb * 1e9 / unit_bytes)
            for _ in range(cpu_procs):
                node.append(n_node)
                gpu.append(False)
                cores.append(host_cores / cpu_procs)
                knee.append(dram_gb * 1e9 / unit_bytes * (host_cores / (sockets * cps)) / cpu_procs)
            n_node += 1
    return (np.asarray(node), np.asarray(gpu), np.asarray(cores, dtype=np.float64),
            np.asarray(knee, dtype=np.float64))


def _speed(config: dict, gpu: np.ndarray, plateau: np.ndarray, knee: np.ndarray,
           x: np.ndarray) -> np.ndarray:
    """True speed at sizes ``x`` ``[p, K]`` (units/s)."""
    unit_bytes = 8.0 * config["unit_block"] ** 2
    l3_units = config["l3_mb"] * 1e6 / unit_bytes
    g = gpu[:, None]
    inside = np.minimum(x, knee[:, None])
    rate_in = np.where(
        g,
        plateau[:, None] * inside / (inside + config["gpu_ramp_units"]),
        plateau[:, None] * (1.0 + config["cpu_cache_boost"] * np.exp(-inside / l3_units)),
    )
    # an overflow unit moves its block out and back in
    out_rate = np.where(g, config["gpu_link_gb_per_s"], config["cpu_page_gb_per_s"]) \
        * 1e9 / (2.0 * unit_bytes)
    t = inside / rate_in + np.maximum(x - knee[:, None], 0.0) / out_rate
    return x / t


def build(config: dict, seed: int):
    """``(xs, ss, groups)``: the knots ``[p, K]`` of every processor's
    speed function and the node of each processor."""
    node, gpu, cores, knee = processors(config)
    rng = np.random.default_rng(seed)
    n_nodes = int(node.max()) + 1
    rank = np.empty(n_nodes, dtype=np.int64)
    rank[rng.permutation(n_nodes)] = np.arange(n_nodes)
    order = np.argsort(rank[node], kind="stable")  # nodes shuffled, members kept together
    node, gpu, cores, knee = rank[node][order], gpu[order], cores[order], knee[order]
    spread = float(config["spread"])
    plateau = np.where(gpu, config["gpu_plateau_units_per_s"],
                       config["cpu_plateau_units_per_s"] * cores / 64.0)
    plateau = plateau * rng.uniform(1.0 - spread, 1.0 + spread, plateau.size)
    fixed = np.asarray(config["knot_units"], dtype=np.float64)
    xs = np.concatenate(
        [np.broadcast_to(fixed, (node.size, fixed.size)),
         knee[:, None] * np.array([0.5, 1.0, 1.25, 2.0])[None, :]], axis=1)
    ss = _speed(config, gpu, plateau, knee, xs)
    return xs, ss, node
