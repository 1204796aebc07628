"""Device time in each chip's build of its own node blocks
(``cola.env_build``) over the part of the traced window the op line
covers, %, mean over devices (``_scopes``). None on one chip, and where
no op carries the scope: a program that builds the env on one device."""
from bench.layer_metrics import _scopes


def read(run):
    if run.chips < 2:
        return None
    return _scopes.device_share(run, "cola.env_build") or None
