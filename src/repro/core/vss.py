"""Feldman verifiable secret sharing for the Shamir scheme (§10).

Shamir sharing (``core/shamir.py``) protects *privacy* but trusts every
committee member to report honest sums — a single tampering member
corrupts the reconstruction silently.  Feldman VSS adds *integrity*:
the dealer publishes commitments ``C_j = h^{a_j}`` to every polynomial
coefficient (``a_0 = v`` the secret), and any verifier can check a
share ``s_w = q(x_w)`` against the public equation

    h^{s_w}  ==  Π_j C_j^{x_w^j}          (in the group <h>)

without learning anything beyond ``h^v``.  Because commitments are
additively homomorphic (``Π_i C_{i,j}`` commits to ``Σ_i a_{i,j}``),
the same equation verifies a committee member's *partial sum* against
the product of all dealers' commitments — which is how a tampering
member (flipped bits, wrong polynomial, replayed round) is caught and
blamed before reconstruction (DESIGN.md §10).

Group choice: the Shamir field is F_p with the Mersenne prime
``p = 2^31 - 1``, so exponent arithmetic must live in a group of order
exactly ``p`` (any other order breaks the identity: shares reduce mod p
but exponents reduce mod the group order).  We use the order-``p``
subgroup of ``F_q^*`` with

    q = 2^59 - 2^28 + 1 = 2^28 * p + 1     (prime; q-1 = 2^28 * p)

whose Crandall structure gives a cheap reduction: ``2^59 ≡ 2^28 - 1
(mod q)``.  Group elements are 59-bit values carried as two ``uint32``
limbs ``(hi, lo)`` — the same TPU-native limb style as
``core/field.py`` (no uint64 anywhere), so the Pallas
``kernels/verify_shares`` family traces these exact jnp sequences and
is bit-identical to this oracle by construction.

Security note: Feldman commitments are computationally hiding only
(``h^v`` leaks the discrete log of the secret's encoding); the paper's
honest-majority privacy argument is unchanged, VSS adds integrity
against tampering, not stronger secrecy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import philox
from .field import MERSENNE_P_INT, mulhilo32, to_field

__all__ = [
    "VSS_GEN_INT", "VSS_ORDER_INT", "VSS_Q_INT", "aggregate_commits",
    "commit_elems", "feldman_commit", "gpow", "np_commit",
    "np_verify_share", "pack", "qadd", "qmul", "qpow_scalar", "to_int",
    "unpack", "verify_share",
]

#: Commitment-group modulus: prime with ``q - 1 = 2^28 * (2^31 - 1)``.
VSS_Q_INT = 2**59 - 2**28 + 1
#: Order of the commitment subgroup — the Shamir field modulus.
VSS_ORDER_INT = MERSENNE_P_INT
#: Generator of the order-p subgroup: ``3^(2^28) mod q``.
VSS_GEN_INT = pow(3, 2**28, VSS_Q_INT)
assert pow(VSS_GEN_INT, VSS_ORDER_INT, VSS_Q_INT) == 1
assert VSS_GEN_INT != 1

_Q_HI = np.uint32(VSS_Q_INT >> 32)            # 0x07FFFFFF
_Q_LO = np.uint32(VSS_Q_INT & 0xFFFFFFFF)     # 0xF0000001
_T28 = np.uint32((1 << 28) - 1)               # 2^59 ≡ 2^28 - 1 (mod q)
_MASK27 = np.uint32((1 << 27) - 1)            # low 27 bits of a hi limb

#: Fixed-base table ``h^(2^i)`` for i = 0..30 (exponents are field
#: elements < p < 2^31), embedded as host uint32 limb constants.
_GEN_POW = np.array(
    [[(pow(VSS_GEN_INT, 1 << i, VSS_Q_INT) >> 32) & 0xFFFFFFFF,
      pow(VSS_GEN_INT, 1 << i, VSS_Q_INT) & 0xFFFFFFFF]
     for i in range(31)], dtype=np.uint32)


def _u32(x):
    return jnp.asarray(x, dtype=jnp.uint32)


def _carry(s, a):
    """Carry bit of the uint32 add ``s = a + b`` as uint32."""
    return (s < a).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# Two-limb F_q arithmetic (value = hi * 2^32 + lo, canonical in [0, q))
# ---------------------------------------------------------------------------

def _cond_sub_q(hi, lo):
    """One conditional subtract of q — finishes every reduction here
    (all intermediate values are kept below 2q by construction)."""
    ge = (hi > _Q_HI) | ((hi == _Q_HI) & (lo >= _Q_LO))
    borrow = (lo < _Q_LO).astype(jnp.uint32)
    return (jnp.where(ge, hi - _Q_HI - borrow, hi),
            jnp.where(ge, lo - _Q_LO, lo))


def qadd(a, b):
    """Group-field add: ``(a + b) mod q`` on (hi, lo) pairs < q."""
    a_hi, a_lo = _u32(a[0]), _u32(a[1])
    b_hi, b_lo = _u32(b[0]), _u32(b[1])
    lo = a_lo + b_lo
    hi = a_hi + b_hi + _carry(lo, a_lo)    # < 2^28: no limb overflow
    return _cond_sub_q(hi, lo)


def qmul(a, b):
    """``(a * b) mod q`` via 16-bit-limb products + Crandall folding.

    The 118-bit product ``V`` is reduced with ``2^59 ≡ t := 2^28 - 1``:
    ``V = A·2^59 + B ≡ A·t + B``; one more fold of ``A·t`` and a final
    conditional subtract land in ``[0, q)``.  Operands must be
    canonical (< q, so hi limbs < 2^27).
    """
    a_hi, a_lo = _u32(a[0]), _u32(a[1])
    b_hi, b_lo = _u32(b[0]), _u32(b[1])
    # full 4-word product w3..w0 (w3 < 2^22 since hi limbs < 2^27)
    h00, l00 = mulhilo32(a_lo, b_lo)
    h01, l01 = mulhilo32(a_lo, b_hi)
    h10, l10 = mulhilo32(a_hi, b_lo)
    h11, l11 = mulhilo32(a_hi, b_hi)
    w0 = l00
    s1 = h00 + l01
    c1 = _carry(s1, h00)
    w1 = s1 + l10
    c1 = c1 + _carry(w1, s1)
    s2 = h01 + h10
    c2 = _carry(s2, h01)
    s2b = s2 + l11
    c2 = c2 + _carry(s2b, s2)
    w2 = s2b + c1
    c2 = c2 + _carry(w2, s2b)
    w3 = h11 + c2
    # A = V >> 59 (two limbs), B = V mod 2^59
    a59_lo = (w1 >> 27) | (w2 << 5)
    a59_hi = (w2 >> 27) | (w3 << 5)
    b59_hi = w1 & _MASK27
    # A*t (three words v2:v1:v0, v2 < 2^23)
    ph, pl = mulhilo32(a59_lo, _T28)
    qh, ql = mulhilo32(a59_hi, _T28)
    v1 = ph + ql
    v2 = qh + _carry(v1, ph)
    # C = (A*t) >> 59 < 2^28 fits one limb; D = (A*t) mod 2^59
    c59 = (v1 >> 27) | (v2 << 5)
    d_hi = v1 & _MASK27
    rh, rl = mulhilo32(c59, _T28)
    # S = C*t + D + B  (s_hi < 2^28: no overflow)
    s_lo = rl + pl
    cc = _carry(s_lo, rl)
    s_lo2 = s_lo + w0
    cc = cc + _carry(s_lo2, s_lo)
    s_hi = rh + d_hi + b59_hi + cc
    # final fold: E = S >> 59 <= 1, S' = (S mod 2^59) + E*t < 2q
    e = s_hi >> 27
    g_lo = s_lo2 + e * _T28
    g_hi = (s_hi & _MASK27) + _carry(g_lo, s_lo2)
    return _cond_sub_q(g_hi, g_lo)


def qpow_scalar(a, e: int):
    """``a^e mod q`` for a *static* Python-int exponent (unrolled)."""
    e = int(e)
    if e < 0:
        raise ValueError(f"exponent must be non-negative, got {e}")
    a_hi, a_lo = _u32(a[0]), _u32(a[1])
    r_hi = jnp.zeros_like(a_hi)
    r_lo = jnp.full_like(a_lo, 1)
    base = (a_hi, a_lo)
    while e > 0:
        if e & 1:
            r_hi, r_lo = qmul((r_hi, r_lo), base)
        e >>= 1
        if e:
            base = qmul(base, base)
    return r_hi, r_lo


def gpow(exponent, *, unroll: bool = False):
    """Fixed-base exponentiation ``h^s`` for uint32 exponents < p.

    Data-dependent square-and-multiply is replaced by the 31 powers
    ``h^(2^i)`` and per-bit selects — fully vectorized over the
    exponent array (this is the per-element hot loop of both commitment
    generation and share verification).

    By default the 31 steps run as a ``fori_loop`` that squares the
    scalar power as it goes: unrolled, XLA's CPU compiler takes minutes
    on the chain.  ``unroll=True`` (the compiled TPU kernel) reads the
    powers from the precomputed table instead.  Both give the same bits.
    """
    s = _u32(exponent)

    def step(i, acc, g):
        m_hi, m_lo = qmul(acc, g)
        take = ((s >> i) & np.uint32(1)) != 0
        return jnp.where(take, m_hi, acc[0]), jnp.where(take, m_lo, acc[1])

    acc = (jnp.zeros_like(s), jnp.full_like(s, 1))
    if unroll:
        for i in range(31):
            acc = step(np.uint32(i), acc, (_GEN_POW[i, 0], _GEN_POW[i, 1]))
        return acc

    def body(i, carry):
        acc, g = carry
        return step(i.astype(jnp.uint32), acc, g), qmul(g, g)

    gen = (jnp.uint32(_GEN_POW[0, 0]), jnp.uint32(_GEN_POW[0, 1]))
    return jax.lax.fori_loop(0, 31, body, (acc, gen))[0]


def pack(hi, lo):
    """(hi, lo) limb pair -> uint32 ``[..., 2]`` (the wire layout)."""
    return jnp.stack([_u32(hi), _u32(lo)], axis=-1)


def unpack(packed):
    """uint32 ``[..., 2]`` -> (hi, lo) limb pair."""
    packed = _u32(packed)
    return packed[..., 0], packed[..., 1]


# ---------------------------------------------------------------------------
# Feldman commit / verify
# ---------------------------------------------------------------------------

def commit_elems(d: int, degree: int) -> int:
    """uint32 elements one dealer's commitment message carries.

    Element-major layout ``[d, degree+1, 2]``: commitments to
    ``a_0..a_degree`` per codeword element, two limbs each — a chunk of
    codeword elements ``[e_lo, e_hi)`` is the contiguous word range
    ``[e_lo*(degree+1)*2, e_hi*(degree+1)*2)`` of the flattened
    message, so commitment traffic chunks on the same element
    boundaries as the share stream (DESIGN.md §8/§10).
    """
    return d * (degree + 1) * 2


def feldman_commit(v, key0, key1, degree: int, counter_base: int = 0):
    """Commit to the Shamir polynomial of ``core.shamir.share``.

    Args:
      v: uint32 field codeword (the encoded secret, any shape) —
        ``a_0`` of the polynomial.
      key0/key1/degree/counter_base: exactly the arguments the matching
        ``shamir.share`` call uses — the coefficients ``a_1..a_d`` are
        re-derived from the same Philox streams (``counter_hi = j+1``,
        same ``counter_base`` chunk offset), so chunked commitments are
        bit-identical slices of the whole-vector commitments.

    Returns:
      uint32 ``[*v.shape, degree+1, 2]`` — ``C_j = h^{a_j}`` per
      element, element-major (see ``commit_elems``).
    """
    v = _u32(v)
    coeffs = [v] + [
        to_field(philox.random_bits_like(v, key0, key1, counter_hi=j + 1,
                                         counter_base=counter_base))
        for j in range(degree)
    ]
    return jnp.stack([pack(*gpow(a)) for a in coeffs], axis=-2)


def aggregate_commits(commits):
    """Pointwise product of dealers' commitments: ``[l, ..., k, 2]`` ->
    ``[..., k, 2]`` — commits to the *sum* polynomial (homomorphism),
    which is what member partial sums verify against."""
    commits = _u32(commits)
    acc = unpack(commits[0])
    for i in range(1, commits.shape[0]):
        acc = qmul(acc, unpack(commits[i]))
    return pack(*acc)


def verify_share(share, commits, point: int):
    """Per-element Feldman check ``h^share == Π_j C_j^{point^j}``.

    Args:
      share: uint32 field elements (any shape) — a share (or partial
        sum of shares) evaluated at ``point``.
      commits: uint32 ``[*share.shape, degree+1, 2]`` — (aggregate)
        commitments, element-major.
      point: the public Shamir evaluation point ``x_w`` (small int).

    Returns:
      bool array of ``share.shape`` — True where the equation holds.
    """
    share = _u32(share)
    commits = _u32(commits)
    k = int(commits.shape[-2])          # degree + 1
    lhs_hi, lhs_lo = gpow(share)
    # Horner in the exponent: Π C_j^{x^j} = C_0 · (C_1 · (...)^x)^x
    acc = unpack(commits[..., k - 1, :])
    for j in range(k - 2, -1, -1):
        acc = qpow_scalar(acc, point)
        acc = qmul(acc, unpack(commits[..., j, :]))
    return (lhs_hi == acc[0]) & (lhs_lo == acc[1])


# ---------------------------------------------------------------------------
# Host-side (Python int) oracles for tests
# ---------------------------------------------------------------------------

def to_int(packed) -> np.ndarray:
    """uint32 ``[..., 2]`` limbs -> object array of Python ints."""
    a = np.asarray(packed, dtype=np.uint64)
    return (a[..., 0].astype(object) * (1 << 32)) + a[..., 1].astype(object)


def np_commit(coeffs) -> np.ndarray:
    """Python-int Feldman commit: list of int arrays -> object [..., k]."""
    cols = []
    for a in coeffs:
        flat = [pow(VSS_GEN_INT, int(x), VSS_Q_INT)
                for x in np.asarray(a).ravel()]
        cols.append(np.array(flat, dtype=object).reshape(np.shape(a)))
    return np.stack(cols, axis=-1)


def np_verify_share(share, commit_ints, point: int) -> np.ndarray:
    """Python-int oracle of ``verify_share`` (object-array commits)."""
    share = np.asarray(share)
    commit_ints = np.asarray(commit_ints, dtype=object)
    out = np.zeros(share.shape, dtype=bool)
    k = commit_ints.shape[-1]
    for idx in np.ndindex(*share.shape):
        rhs = int(commit_ints[idx + (k - 1,)])
        for j in range(k - 2, -1, -1):
            rhs = pow(rhs, point, VSS_Q_INT)
            rhs = rhs * int(commit_ints[idx + (j,)]) % VSS_Q_INT
        out[idx] = pow(VSS_GEN_INT, int(share[idx]), VSS_Q_INT) == rhs
    return out
