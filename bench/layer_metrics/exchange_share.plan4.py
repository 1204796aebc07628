"""Device time in the gossip exchange (``cola.exchange``: the
collective-permutes and the neighbourhood buffer they fill) over the part
of the traced window the op line covers, %, mean over devices
(``_scopes``). None on one chip."""
from bench.layer_metrics import _scopes


def read(run):
    if run.chips < 2:
        return None
    return _scopes.device_share(run, "cola.exchange")
