"""Mean host time of the group aggregation: sample times, member allocations
and the aggregate bank (the program's ``hier.aggregate`` span), per
repartition."""


def read(run):
    spans = run.spans.get("hier.aggregate", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
