"""Mean time from the dispatch of the two-level route's inner program until its
result is on the host (the program's ``hier.inner.wait`` span), per
repartition."""


def read(run):
    spans = run.spans.get("hier.inner.wait", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
