"""What every family's operation and byte counts share.  Kept with the
benchmark so that every PR counts them the same way; a family's
``sequence_flops`` and ``kernel_cost`` (``chipbench/families/``) say what
they count for its architecture.
"""

from __future__ import annotations


def causal_pairs(start: int, end: int) -> int:
    """(query, key) pairs of the positions ``start .. end - 1`` attending
    causally from position 0."""
    return (end * (end + 1) - start * (start + 1)) // 2


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the longer of compute at peak and traffic at peak."""
    return max(ops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
