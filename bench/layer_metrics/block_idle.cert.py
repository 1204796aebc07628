"""Device idle time inside the block spans (``block-first-dispatch``,
``block-dispatch``, ``stop-sync``, ``history-fetch``),
over the part of the traced window the device's trace covers, %, mean over
devices (``_scopes``)."""
from bench.layer_metrics import _scopes


def read(run):
    return _scopes.idle_in_spans(run.trace, _scopes.BLOCK_SPANS)
