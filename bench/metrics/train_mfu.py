"""The whole training step's share of the chips' bf16 peak: points
trained per second in the traced window, times the nearest-code search
each point needs (``work/nearest_search.py``), over chips times peak."""


def read(run):
    points = run.counters.get("traced_points_per_worker", 0) * run.chips
    if points <= 0 or run.summary.window_s <= 0:
        return None
    work = run.load_work("nearest_search")
    flops = points * work.flops_per_point(run.config["kappa"], run.config["d"])
    return 100.0 * flops / (run.summary.window_s * run.chips
                            * run.peaks["flops_bf16"])
