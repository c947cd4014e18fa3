"""Seeded per-round cohort sampling over a large party registry.

Production FL samples a per-round *cohort* from a huge registered
population instead of driving every registered party through every
round (IBM FL white paper; the GDPR-era survey in PAPERS.md).  This
module is the single source of that schedule: the sim transport, the
FedAvg driver, the wire coordinator, and the Eq. 3–6 per-cohort
counter mirror all call :func:`sample_cohort` with the same arguments,
which is what keeps sim and wire bit-identical per cohort.

The draw is Philox-derived and keyed per *party id*, not per position:
party ``i``'s rank for round ``r`` is ``random_bits[i]`` from the
stream ``derive_key(seed, COHORT_STREAM)`` with
``counter_hi = COHORT_COUNTER_HI + r``.  Ranks therefore do not shift
when the eligible set churns — registering, deregistering, or banning
*other* parties never changes whether party ``i`` would rank into the
cohort, so registry churn between rounds keeps the schedule (and the
closed-form mirror) exact on both backends.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import philox

__all__ = ["CohortConfig", "CohortExhaustedError", "assign_home",
           "sample_cohort", "COHORT_STREAM", "COHORT_COUNTER_HI",
           "HOME_STREAM", "HOME_COUNTER_HI"]

#: Philox stream id of the cohort schedule — disjoint by key derivation
#: from the election streams ``(r << 20) | id`` (different ``stream``
#: argument to ``derive_key`` → unrelated key pair).
COHORT_STREAM = 0xC0_4057
#: counter_hi tag; the per-round offset rides on top of it.
COHORT_COUNTER_HI = 0x11_0000
#: Philox stream id of the home-member assignment (tree relay,
#: DESIGN.md §13) — its own ``derive_key`` stream, disjoint from the
#: cohort, election, and commitment streams.
HOME_STREAM = 0x40_73EE
#: counter_hi tag of the home-member draw; per-round offset on top.
HOME_COUNTER_HI = 0x12_0000


class CohortExhaustedError(RuntimeError):
    """No eligible party remains to sample a cohort from.

    Raised when the eligible pool is empty — e.g. every registered
    party has been banned by the blame paths or every lease expired.
    Callers must let this propagate (a round cannot run without a
    cohort); it is re-raised cleanly through the transport layers.
    """


@dataclasses.dataclass(frozen=True)
class CohortConfig:
    """Per-round cohort sampling parameters.

    ``size`` — target cohort size ``c``; when fewer than ``c`` parties
    are eligible the cohort shrinks to the whole eligible set (an empty
    eligible set raises :class:`CohortExhaustedError`).
    """
    size: int

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"cohort size={self.size} must be >= 1")


def sample_cohort(eligible_ids, size: int, seed: int,
                  round_index: int) -> tuple[int, ...]:
    """Sample the round's cohort: ``size`` eligible ids, sorted.

    Every eligible id draws one uint32 rank from the round's cohort
    stream; the ``size`` lowest ranks (ties broken by id) form the
    cohort.  Deterministic in ``(seed, round_index, eligible set)`` and
    stable per id under churn of the rest of the pool.
    """
    ids = sorted({int(i) for i in eligible_ids})
    if not ids:
        raise CohortExhaustedError(
            f"round {round_index}: no eligible parties to sample a "
            f"cohort of {size} from (all registered parties banned, "
            f"evicted, or expired)")
    if any(i < 0 for i in ids):
        raise ValueError(f"negative party id in eligible set: {ids[0]}")
    if len(ids) <= size:
        return tuple(ids)
    k0, k1 = philox.derive_key(seed, COHORT_STREAM)
    bits = np.asarray(philox.random_bits(
        ids[-1] + 1, k0, k1,
        counter_hi=COHORT_COUNTER_HI + round_index))
    ranked = sorted(ids, key=lambda i: (int(bits[i]), i))
    return tuple(sorted(ranked[:size]))


def assign_home(party_ids, committee, seed: int,
                round_index: int) -> dict[int, int]:
    """Assign each party a *home* committee member for the tree relay.

    Like :func:`sample_cohort`, the draw is keyed per party id, not per
    position: party ``i``'s home for round ``r`` is
    ``sorted(committee)[bits[i] % m]`` with ``bits`` drawn from the
    ``HOME_STREAM`` Philox stream at ``counter_hi = HOME_COUNTER_HI +
    r`` — so churn in the rest of the cohort never moves a surviving
    party's home, and coordinator and members recompute the same map
    independently.  Members may be their own home (they fold their own
    upload locally, no extra socket).
    """
    ids = sorted({int(i) for i in party_ids})
    members = sorted({int(w) for w in committee})
    if not members:
        raise ValueError("assign_home needs a non-empty committee")
    if not ids:
        return {}
    if any(i < 0 for i in ids):
        raise ValueError(f"negative party id in cohort: {ids[0]}")
    k0, k1 = philox.derive_key(seed, HOME_STREAM)
    # a power-of-two draw: word i is the same whatever the length, and
    # the cohort's largest id then picks one of few compiled shapes
    bits = np.asarray(philox.random_bits(
        1 << ids[-1].bit_length(), k0, k1,
        counter_hi=HOME_COUNTER_HI + round_index))
    return {i: members[int(bits[i]) % len(members)] for i in ids}
