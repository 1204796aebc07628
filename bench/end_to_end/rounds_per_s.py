"""All rounds completed in the window over the window's time."""


def read(run):
    if not run.solves:
        return None
    return sum(s.rounds for s in run.solves) / run.window_s
