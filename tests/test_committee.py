"""Phase I committee election (Alg. 2) properties."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import committee


@given(st.integers(min_value=3, max_value=40),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=1000))
@settings(max_examples=25)
def test_election_validity(n, m, seed):
    m = min(m, n)
    res = committee.elect(n=n, m=m, b=16, seed=seed)
    assert len(res.committee) == m
    assert len(set(res.committee)) == m
    assert all(0 <= c < n for c in res.committee)


def test_election_deterministic():
    a = committee.elect(n=16, m=3, b=10, seed=42)
    b = committee.elect(n=16, m=3, b=10, seed=42)
    assert a.committee == b.committee
    c = committee.elect(n=16, m=3, b=10, seed=43)
    # different seed usually differs (not guaranteed; just sanity)
    assert isinstance(c.committee, tuple)


def test_election_unbiased_coarse():
    """Over many seeds, every party should get elected sometimes."""
    hits = np.zeros(8)
    for seed in range(60):
        for c in committee.elect(n=8, m=3, b=10, seed=seed).committee:
            hits[c] += 1
    assert (hits > 0).all()


def test_tally_matches_votes():
    total = np.array([3, 3, 5, 0, 1], dtype=np.uint32)
    t = committee.tally_votes(total, n=4)
    # 3%4=3 (x2), 5%4=1, 0%4=0, 1%4=1
    np.testing.assert_array_equal(t, [1, 2, 0, 2])


def test_committee_too_large_raises():
    with pytest.raises(ValueError):
        committee.elect(n=3, m=5, b=10, seed=0)
