"""Device time in the round's local solve (``cola.local_solve``)
over the part of the traced window the op line covers, %, mean over
devices (``_scopes``)."""
from bench.layer_metrics import _scopes


def read(run):
    return _scopes.device_share(run, _scopes.SOLVE)
