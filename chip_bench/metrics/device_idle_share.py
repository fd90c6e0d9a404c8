"""1 - (union of device-op intervals / traced window), in %: the time the
chip waited on the host. Moves ``real_tokens_per_s``."""


def read(w):
    if w.trace is None or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
