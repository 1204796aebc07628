"""The window's rounds' least time by roofline on the cell's chips over
the traced window (``_shares.roofline``). None on one chip."""
from bench.layer_metrics import _shares


def read(run):
    return None if run.chips < 2 else _shares.roofline(run)
