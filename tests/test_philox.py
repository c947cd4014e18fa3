"""Philox4x32-10 counter PRNG: oracle equality, stream separation."""

import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import philox

U32 = st.integers(min_value=0, max_value=2**32 - 1)


@given(U32, U32, st.integers(min_value=1, max_value=32))
@settings(max_examples=25)
def test_matches_numpy_oracle(k0, k1, n):
    counters = np.arange(4 * n, dtype=np.uint32).reshape(n, 4)
    got = np.asarray(philox.philox_4x32(jnp.asarray(counters),
                                        np.uint32(k0), np.uint32(k1)))
    want = philox.np_philox_4x32(counters, k0, k1)
    np.testing.assert_array_equal(got, want)


def test_random_bits_deterministic_and_lengths():
    for n in [1, 3, 4, 7, 128, 1000]:
        a = philox.random_bits(n, np.uint32(1), np.uint32(2))
        b = philox.random_bits(n, np.uint32(1), np.uint32(2))
        assert a.shape == (n,)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streams_differ():
    a = philox.random_bits(256, np.uint32(1), np.uint32(2), counter_hi=1)
    b = philox.random_bits(256, np.uint32(1), np.uint32(2), counter_hi=2)
    assert (np.asarray(a) != np.asarray(b)).mean() > 0.99


def test_tiled_words_layout():
    """tiled_words must equal the per-(row,lane) counter convention."""
    rows = 4
    out = np.asarray(philox.tiled_words(rows, np.uint32(9), np.uint32(7),
                                        counter_hi=3, row_base=10))
    for r in range(rows):
        for lb in range(32):
            c = np.array([[(10 + r) * 32 + lb, 3, 0, 0]], np.uint32)
            words = philox.np_philox_4x32(c, 9, 7)[0]
            np.testing.assert_array_equal(out[r, lb * 4:(lb + 1) * 4], words)


def test_uniformity_coarse():
    bits = np.asarray(philox.random_bits(1 << 14, np.uint32(5),
                                         np.uint32(6)))
    ones = np.unpackbits(bits.view(np.uint8)).mean()
    assert abs(ones - 0.5) < 0.01


def test_derive_key_traced_and_static_agree():
    import jax
    k_static = philox.derive_key(42, 7)
    k_traced = jax.jit(lambda s: philox.derive_key(42, s))(jnp.int32(7))
    assert int(k_static[0]) == int(k_traced[0])
    assert int(k_static[1]) == int(k_traced[1])
