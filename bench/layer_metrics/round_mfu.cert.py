"""Operations of the window's rounds over window time x chips x bf16 peak."""
from bench.layer_metrics import _shares

read = _shares.mfu
