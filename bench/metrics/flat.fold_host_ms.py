"""Mean host time of a fold's list conversions and scalar ``add_point``
loop (the program's ``speedstore.fold_host`` span), per round."""


def read(run):
    spans = run.spans.get("speedstore.fold_host", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
