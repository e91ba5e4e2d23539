"""Share of the window's rounds whose allocation changed: the rounds in
which the scheduler's work reached the processors."""


def read(run):
    if not run.rounds:
        return None
    return 100.0 * sum(r.changed for r in run.rounds) / len(run.rounds)
