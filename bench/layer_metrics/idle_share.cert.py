"""1 - (union of the device's program intervals) / the part of the traced
window its trace covers, mean over devices."""
from bench.layer_metrics import _shares

read = _shares.idle
