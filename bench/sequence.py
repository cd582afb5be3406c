"""Request sequences that every seed shares."""
from __future__ import annotations

from fractions import Fraction


def balanced(weights: dict, length: int) -> list:
    """``length`` items drawn from ``weights`` (item -> probability) so
    that every prefix holds each item as close to its share as whole
    counts allow: at each step the item furthest behind its share comes
    next (ties to the earlier item).  The same for every seed, so that
    seeds change the values of requests and not the work in a window."""
    share = {k: Fraction(v).limit_denominator(1000)
             for k, v in weights.items()}
    counts = dict.fromkeys(share, 0)
    out = []
    for i in range(1, length + 1):
        k = max(share, key=lambda k: share[k] * i - counts[k])
        counts[k] += 1
        out.append(k)
    return out
