"""Device time in the local solve's Gram kernel (``cola.cd_kernel``,
nested in ``cola.local_solve``) over the part of the traced window the op
line covers, %, mean over devices (``_scopes``). None where no op carries
the scope: a program that runs the solve as a scan."""
from bench.layer_metrics import _scopes


def read(run):
    return _scopes.device_share(run, "cola.cd_kernel") or None
