"""How the architecture files (``archs/``) count one serving step's
operations and bytes, and the terms they share.

A step is a list of ``(n, end)`` pairs, one for each slot that lands
tokens: ``n`` real tokens at positions ``end - n .. end - 1``, plus
``emit``, the rows that reach the head.  A multiply-add counts two
operations.

Bytes are the least any program must move, at the published model's
precisions whatever the program stores: ternary weights at 2 bits plus a
2-byte scale per output channel, norm weights, embedding rows and the head
at 2 bytes, each slot's earlier keys and values read once and the new ones
written once, at 2 bytes.  A program that keeps wider types moves more, so
its share of this bound can only read lower, never above 100%.
"""
from __future__ import annotations

BF16 = 2


def context_sum(n: int, end: int) -> int:
    """Sum over positions end-n .. end-1 of (position + 1)."""
    return n * end - n * (n - 1) // 2
