"""Share of the traced stretch in which no program ran on the device:
``1 - busy / window``, busy being the union of program executions."""


def read(run):
    if run.device is None:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])
