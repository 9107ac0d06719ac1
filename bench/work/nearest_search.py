"""The work every training point needs whatever the implementation: its
nearest-code search, one (1, d) x (d, kappa) product.  ``train_mfu``
counts this alone, so it cannot pass 100% by recomputation, by the eval
probe, or by a kernel that covers more windows per call."""


def flops_per_point(kappa: int, d: int) -> float:
    return 2.0 * kappa * d
