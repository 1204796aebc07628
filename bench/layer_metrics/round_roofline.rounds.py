"""The window's rounds' least time by roofline over the traced window."""
from bench.layer_metrics import _shares

read = _shares.roofline
