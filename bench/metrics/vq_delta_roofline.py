"""Share of the per-step kernel's roofline: the least time the traced
points need (``work/vq_delta.py``, one point per call) over the device
time of the ``vq_delta`` kernel's events, per chip."""


def read(run):
    kernel_s = run.summary.kernel_s("vq_delta")
    points = run.counters.get("traced_points_per_worker", 0)
    if kernel_s <= 0 or points <= 0:
        return None
    work = run.load_work("vq_delta")
    shape = (run.config["kappa"], run.config["d"])
    least = points * max(
        work.flops_per_point(*shape) / run.peaks["flops_bf16"],
        work.bytes_per_point(*shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
