"""Secret-sharing invariants (additive + Shamir), property-based."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import additive, philox, shamir
from repro.core.aggregation import SecureAggregator
from repro.core.field import MERSENNE_P_INT
from repro.core.fixed_point import FixedPointConfig


@given(st.integers(min_value=1, max_value=12),
       st.integers(min_value=0, max_value=2**31))
@settings(max_examples=25)
def test_additive_roundtrip(m, seed_val):
    rng = np.random.RandomState(seed_val % 100000)
    v = rng.randint(0, 2**32, size=257, dtype=np.uint64).astype(np.uint32)
    k0, k1 = philox.derive_key(seed_val, 0)
    shares = additive.share(jnp.asarray(v), m, k0, k1)
    assert shares.shape == (m, 257)
    np.testing.assert_array_equal(
        np.asarray(additive.reconstruct(shares)), v)


@given(st.integers(min_value=2, max_value=8))
@settings(max_examples=10)
def test_additive_single_share_reveals_nothing_structural(m):
    """Shares of two different secrets have identical marginal streams
    for the mask shares (they ARE the Philox stream)."""
    k0, k1 = philox.derive_key(7, 1)
    v1 = jnp.zeros(64, jnp.uint32)
    v2 = jnp.full((64,), 12345, jnp.uint32)
    s1 = additive.share(v1, m, k0, k1)
    s2 = additive.share(v2, m, k0, k1)
    # all mask shares identical; only the last share differs
    np.testing.assert_array_equal(np.asarray(s1[:-1]), np.asarray(s2[:-1]))
    assert (np.asarray(s1[-1]) != np.asarray(s2[-1])).any()


@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=0, max_value=10000))
@settings(max_examples=20)
def test_shamir_roundtrip(m, seed_val):
    rng = np.random.RandomState(seed_val)
    v = rng.randint(0, MERSENNE_P_INT, size=130,
                    dtype=np.uint64).astype(np.uint32)
    k0, k1 = philox.derive_key(seed_val, 3)
    shares = shamir.share(jnp.asarray(v), m, k0, k1)
    np.testing.assert_array_equal(np.asarray(shamir.reconstruct(shares)), v)


def test_shamir_threshold_subsets():
    rng = np.random.RandomState(0)
    v = rng.randint(0, MERSENNE_P_INT, size=64,
                    dtype=np.uint64).astype(np.uint32)
    k0, k1 = philox.derive_key(1, 1)
    m, d = 6, 2
    shares = shamir.share(jnp.asarray(v), m, k0, k1, degree=d)
    # any d+1 = 3 shares suffice
    for subset in [(0, 1, 2), (1, 3, 5), (2, 4, 5), (0, 3, 4)]:
        pts = tuple(i + 1 for i in subset)
        rec = shamir.reconstruct(shares[jnp.asarray(subset)], points=pts)
        np.testing.assert_array_equal(np.asarray(rec), v)
    # d shares do NOT reconstruct (wrong result almost surely)
    rec2 = shamir.reconstruct(shares[jnp.asarray([0, 1])], points=(1, 2))
    assert (np.asarray(rec2) != v).any()


@given(st.integers(min_value=2, max_value=10),
       st.sampled_from(["additive", "shamir"]))
@settings(max_examples=12)
def test_secure_mean_equals_plain_mean(n, scheme):
    rng = np.random.RandomState(n)
    flats = [jnp.asarray(rng.randn(101).astype(np.float32))
             for _ in range(n)]
    agg = SecureAggregator(scheme=scheme, m=min(3, n))
    mean = agg.aggregate_reference(flats, seed=42)
    ref = np.mean([np.asarray(f) for f in flats], axis=0)
    bound = agg.fp.quant_error_bound(n) / n + 1e-6
    assert np.abs(np.asarray(mean) - ref).max() <= bound * 1.01 + 2 ** -16


def test_headroom_validation():
    agg = SecureAggregator(scheme="additive", m=3)
    with pytest.raises(ValueError):
        agg.fp.validate_for_parties(10 ** 6)


def test_fixed_point_roundtrip_and_bias():
    cfg = FixedPointConfig(frac_bits=16, clip=8.0)
    x = jnp.asarray(np.linspace(-7.9, 7.9, 1001, dtype=np.float32))
    rt = cfg.decode(cfg.encode(x))
    assert np.abs(np.asarray(rt) - np.asarray(x)).max() <= 0.5 / cfg.scale
    # clipping
    y = cfg.decode(cfg.encode(jnp.asarray([100.0], jnp.float32)))
    assert float(y[0]) == pytest.approx(8.0, abs=1e-3)
