"""Device idle time inside a solve's set-up spans (``env-build``,
``recorder-setup``, ``schedule-build``, ``driver-build``),
over the part of the traced window the device's trace covers, %, mean over
devices (``_scopes``)."""
from bench.layer_metrics import _scopes


def read(run):
    return _scopes.idle_in_spans(run.trace, _scopes.SETUP_SPANS)
