"""Device time of the partition program (``jit__partition_units_impl``) per
round of the traced stretch, from the profiler trace."""


def read(run):
    if run.device is None or not run.rounds_traced:
        return None
    s = run.device["programs_s"].get("jit__partition_units_impl")
    if not s:
        return None
    return 1e3 * s / run.rounds_traced
