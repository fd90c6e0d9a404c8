"""The least time the attention work of the window's real tokens needs
(the larger of its FLOPs over the bf16 peak and its bytes over HBM
bandwidth, ``chip_bench/flops.py``) over the device time of the Pallas
kernels in the trace, in %. Moves ``real_tokens_per_s``."""
import numpy as np

from chip_bench import flops


def read(w):
    if w.trace is None or w.trace["pallas_s"] <= 0 or not w.iterations:
        return None
    lengths = np.concatenate([it["lengths"] for it in w.iterations])
    f, b = flops.attention_work(w.model, lengths)
    return 100.0 * flops.roofline_seconds(f, b, w.peak) / w.trace["pallas_s"]
