"""Mean time the runner's main loop waited on the plan of a window
iteration (the runner's own ``plan_wait`` measurement), in ms: planning
that plan-ahead did not hide. Moves ``step_ms_p90``."""


def read(w):
    if not w.iterations:
        return None
    return 1e3 * sum(it["plan_wait_s"] for it in w.iterations) \
        / len(w.iterations)
