"""Least work of one fused window (``tau`` sequential eq.-1 steps on one
chip), from the cell's shapes, not the kernel's calls.

Each step scores its point against the whole codebook: 2 * kappa * d
operations.  The codebook itself need not cross HBM: it fits the chip's
on-core memory, and the compiled program keeps it there between calls
(the trace shows the kernel's codebook operand in memory space 1), so
the least HBM traffic is the window's points streamed in.  Counting a
codebook round trip per window would let a kernel that keeps it resident
across windows read over 100% of this roofline."""


def flops_per_window(kappa: int, d: int, tau: int) -> float:
    return 2.0 * tau * kappa * d


def bytes_per_window(kappa: int, d: int, tau: int) -> float:
    return tau * d * 4.0
