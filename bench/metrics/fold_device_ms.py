"""Device time of the fold program (``jit__fold_in_impl``, the donating and
the non-donating twin compile to the same name) per round of the traced
stretch, from the profiler trace."""


def read(run):
    if run.device is None or not run.rounds_traced:
        return None
    s = run.device["programs_s"].get("jit__fold_in_impl")
    if not s:
        return None
    return 1e3 * s / run.rounds_traced
