"""Mean host time of the EMA loop in ``Scheduler.observe`` (the program's
``scheduler.ema`` span), per served round."""


def read(run):
    spans = run.spans.get("scheduler.ema", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
