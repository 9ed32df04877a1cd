"""Operations and bytes that a kernel's roofline share is read against.

A ``<kernel>_roofline`` metric divides this least work by the kernel's
device time and the chip's peak (``peaks.py``), so the count has to be the
least that any implementation of the kernel must do: an implementation that
moves more bytes reads a lower share, never one above 100%.
"""
from __future__ import annotations


def update_bytes(params: int, groups: int, clients: int,
                 itemsize: int = 4) -> int:
    """Least HBM bytes of one corrected local step of MTGC,
    ``x <- x - lr * (g + z + y)``, over ``groups x clients`` replicas of a
    model of ``params`` numbers.

    Every replica reads its model x, its gradient g and its correction z
    and writes x back; y is one per group, read once. ``itemsize`` is the
    bytes of one number of the state (4 for float32).
    """
    replicas = groups * clients
    return itemsize * params * (4 * replicas + groups)


def update_flops(params: int, groups: int, clients: int) -> int:
    """Floating-point operations of the same step: per number of every
    replica, two additions, one multiplication and one subtraction."""
    return 4 * params * groups * clients
