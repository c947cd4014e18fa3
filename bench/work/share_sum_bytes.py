"""HBM bytes one round of the party engine needs, from shapes.

l parties' D-element updates read once (4lD), the m committee members'
sums written once (4mD) and read once to reconstruct (4mD), the mean
written once (4D): the least a fused share, fold and reconstruct moves.
Bound by HBM, not by FLOPs.
"""


def bytes_per_round(parties: int, m: int, d: int) -> float:
    return float(4 * parties * d + 8 * m * d + 4 * d)
