"""Mean host time of the program's ``speedstore.partition`` span (the
jitted solve and its host read), per round that repartitioned."""


def read(run):
    spans = run.spans.get("speedstore.partition", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
