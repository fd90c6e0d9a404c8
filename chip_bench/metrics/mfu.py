"""Model FLOPs of the window's real tokens over window seconds x chips x
the chip's bf16 peak, in %: the whole step's share of the peak (see
``chip_bench/flops.py`` for what is counted). Moves ``real_tokens_per_s``."""
from chip_bench import harness


def read(w):
    if not w.iterations or w.seconds <= 0:
        return None
    return 100.0 * harness.model_flops(w) / (
        w.seconds * w.chips * w.peak["bf16_flops_per_s"])
