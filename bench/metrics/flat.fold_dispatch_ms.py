"""Mean host time of the device bank's fold: the uploads, the width check
and the kernel dispatch, not the device's execution (the program's
``modelbank.fold_in`` span), per round."""


def read(run):
    spans = run.spans.get("modelbank.fold_in", [])
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
