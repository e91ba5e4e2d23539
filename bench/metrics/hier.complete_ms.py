"""Mean host time of the outer level's integer shares: floor, take-back and the
completion of the leftover units (the program's ``hier.outer.complete``
span), per repartition."""


def read(run):
    spans = run.spans.get("hier.outer.complete", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
