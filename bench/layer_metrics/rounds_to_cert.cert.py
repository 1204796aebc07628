"""Rounds run to the certified stop (stop_round + 1) of the window's last
certified solve: a count, from the recorder's history."""


def read(run):
    stops = [s.stop_round for s in run.solves if s.certified]
    return None if not stops else float(stops[-1] + 1)
