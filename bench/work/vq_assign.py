"""Least work of one per-step ``vq_assign`` call (a batch of one point):
the point is scored against the whole codebook, 2 * kappa * d
operations.  As for the window kernel (``vq_window.py``), the codebook
and its row norms stay in on-core memory between steps, so the least HBM
traffic is the point streamed in."""


def flops_per_point(kappa: int, d: int) -> float:
    return 2.0 * kappa * d


def bytes_per_point(kappa: int, d: int) -> float:
    return d * 4.0
