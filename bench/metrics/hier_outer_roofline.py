"""Share of v5e's HBM roofline that the two-level route's continuous outer
solve reaches: the least bytes the program must move, its inputs and outputs
(``outer_bytes``), over its device time per repartition
(``jit__partition_continuous_jit`` over the repartitions the traced stretch
counts, see ``hier_inner_device_ms``), against 819 GB/s.  The loop records
each session's ``(groups, max_group_knots)``."""

HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}  # Google Cloud, "TPU v5e"


def outer_bytes(groups: int, max_group_knots: int) -> int:
    """Inputs and outputs of one outer solve: the aggregate bank's knots and
    speeds ``[g, k]`` (float64, ``k = 3 * max(max_group_knots // 4, 2)``, the
    aggregate's knot bound), its counts (int64) and caps; the ``[g]``
    allocations, ``t*`` and the two trip counts."""
    k = 3 * max(max_group_knots // 4, 2)
    return 2 * groups * k * 8 + groups * 8 + groups * 8 + 3 * 8 + groups * 8 + 8 + 2 * 4


def read(run):
    if run.device is None or not run.device.get("rounds") or not run.sessions:
        return None
    shape = getattr(run.sessions[-1], "outer_shape", None)
    s = run.device["programs_s"].get("jit__partition_continuous_jit")
    if shape is None or not s:
        return None
    import jax

    peak = HBM_BYTES_PER_S[jax.devices()[0].device_kind]  # no default for another chip
    per_call = s / run.device["rounds"]
    return 100.0 * outer_bytes(*shape) / peak / per_call
