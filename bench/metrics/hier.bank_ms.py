"""Mean host time of the read of the store's device carry and the group slicing
before a two-level repartition (the program's ``scheduler.hier_bank`` span),
per repartition."""


def read(run):
    spans = run.spans.get("scheduler.hier_bank", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
