"""Device time in the round's update (``cola.update``: x, the A dx
einsum, v) over the part of the traced window the op line covers, %, mean
over devices (``_scopes``)."""
from bench.layer_metrics import _scopes


def read(run):
    return _scopes.device_share(run, _scopes.UPDATE)
