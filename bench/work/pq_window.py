"""Least work of one fused product-quantizer window (``tau`` sequential
eq.-1 steps in each of ``m`` sub-spaces on one chip), from the cell's
shapes, not the kernel's calls.

Each step scores each of its m sub-vectors against that sub-space's
``kappa`` codes: m * kappa * (d / m) * 2 = 2 * kappa * d operations, the
same count as one (kappa, d) search.  The sub-codebooks (4 * kappa * d
bytes, 128 KiB at PQ16x256) stay in on-core memory between calls, so the
least HBM traffic is the window's points streamed in."""


def flops_per_window(kappa: int, d: int, tau: int) -> float:
    return 2.0 * tau * kappa * d


def bytes_per_window(kappa: int, d: int, tau: int) -> float:
    return tau * d * 4.0
