"""Real (non-pad) tokens over the tokens the window's plans padded them to,
in %: the planner's and palette's padding waste. Counted from the plans the
runner obtained; moves ``real_tokens_per_s``."""


def read(w):
    padded = sum(it["padded"] for it in w.iterations)
    if not padded:
        return None
    return 100.0 * sum(it["tokens"] for it in w.iterations) / padded
