"""Mean host time of ``Scheduler(...)`` construction, store included, per
session started in the window (benchmark span around the call)."""


def read(run):
    spans = [s.construct for s in run.sessions if s.construct[0] >= run.t0]
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
