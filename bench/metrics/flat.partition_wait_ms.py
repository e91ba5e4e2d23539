"""Mean time from the dispatch of the partition program until its results
are on the host (the program's ``modelbank.partition.wait`` span), per
repartition."""


def read(run):
    spans = run.spans.get("modelbank.partition.wait", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
