"""Device time of the two-level route's inner program
(``jit__hier_inner_map``) per repartition of the traced stretch, from the
profiler trace; the loop runs each measurement that follows a repartition
under a ``bench.round`` annotation, which the trace counts."""


def read(run):
    if run.device is None or not run.device.get("rounds"):
        return None
    s = run.device["programs_s"].get("jit__hier_inner_map")
    if not s:
        return None
    return 1e3 * s / run.device["rounds"]
