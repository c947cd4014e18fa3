"""Mersenne-31 field + Z_2^32 ring arithmetic vs exact numpy oracles."""

import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import field

U32 = st.integers(min_value=0, max_value=2**32 - 1)
FP = st.integers(min_value=0, max_value=field.MERSENNE_P_INT - 1)


@given(st.lists(U32, min_size=1, max_size=64),
       st.lists(U32, min_size=1, max_size=64))
@settings(max_examples=50)
def test_mulhilo32_matches_uint64(xs, ys):
    n = min(len(xs), len(ys))
    a = np.array(xs[:n], np.uint32)
    b = np.array(ys[:n], np.uint32)
    hi, lo = field.mulhilo32(jnp.asarray(a), jnp.asarray(b))
    prod = a.astype(np.uint64) * b.astype(np.uint64)
    np.testing.assert_array_equal(np.asarray(hi),
                                  (prod >> np.uint64(32)).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(lo),
                                  (prod & np.uint64(0xFFFFFFFF)).astype(np.uint32))


@given(st.lists(FP, min_size=1, max_size=64),
       st.lists(FP, min_size=1, max_size=64))
@settings(max_examples=50)
def test_fmul_fadd_match_oracle(xs, ys):
    n = min(len(xs), len(ys))
    a = np.array(xs[:n], np.uint32)
    b = np.array(ys[:n], np.uint32)
    np.testing.assert_array_equal(
        np.asarray(field.fmul(jnp.asarray(a), jnp.asarray(b))),
        field.np_fmul(a, b))
    np.testing.assert_array_equal(
        np.asarray(field.fadd(jnp.asarray(a), jnp.asarray(b))),
        field.np_fadd(a, b))


@given(FP)
@settings(max_examples=30)
def test_fsub_fneg_inverse(x):
    a = jnp.asarray([x], jnp.uint32)
    zero = field.fadd(a, field.fneg(a))
    assert int(zero[0]) == 0
    assert int(field.fsub(a, a)[0]) == 0


@given(st.integers(min_value=1, max_value=field.MERSENNE_P_INT - 1))
@settings(max_examples=20)
def test_finv(x):
    a = jnp.asarray([x], jnp.uint32)
    one = field.fmul(a, field.finv(a))
    assert int(one[0]) == 1


def test_mersenne_reduce_edge_cases():
    p = field.MERSENNE_P_INT
    for v, want in [(0, 0), (p, 0), (p + 1, 1), (2**32 - 1, 2**32 - 1 - 2 * p)]:
        got = int(field.mersenne_reduce(jnp.asarray([v], jnp.uint32))[0])
        assert got == want % p, (v, got)


def test_ring_wraparound():
    a = jnp.asarray([2**32 - 1], jnp.uint32)
    assert int(field.ring_add(a, jnp.asarray([1], jnp.uint32))[0]) == 0
    assert int(field.ring_sub(jnp.asarray([0], jnp.uint32), a)[0]) == 1
