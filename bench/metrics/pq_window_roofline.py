"""Share of the product-quantizer window kernel's roofline: the least time
the traced windows need (``work/pq_window.py``, from the cell's shapes)
over the device time of the ``pq_window`` kernel's events, per chip."""


def read(run):
    kernel_s = run.summary.kernel_s("pq_window")
    windows = run.counters.get("traced_points_per_worker", 0) // run.config["tau"]
    if kernel_s <= 0 or windows <= 0:
        return None
    work = run.load_work("pq_window")
    shape = (run.config["kappa"], run.config["d"], run.config["tau"])
    least = windows * max(
        work.flops_per_window(*shape) / run.peaks["flops_bf16"],
        work.bytes_per_window(*shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
