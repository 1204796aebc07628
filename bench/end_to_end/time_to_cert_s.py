"""Wall time of all certified solves of the window over their count."""


def read(run):
    if run.traffic["kind"] != "certified_solves" or not run.solves:
        return None
    return run.window_s / len(run.solves)
