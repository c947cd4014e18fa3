"""HBM bytes one party's secure aggregation needs per step, from shapes.

Over the D-element gradient with an m-member committee: read the
gradient (4D), write the m shares (4mD), read the summed shares (4mD),
write the mean (4D).  The count is of what the algorithm must move, so
no fusion or restructuring of the kernels can push a share of it past
the HBM bound.  At these sizes the work is bound by HBM, not by FLOPs.
"""


def bytes_per_party_step(d: int, m: int) -> float:
    return float(8 * d + 8 * m * d)
