"""Counter-based Philox4x32-10 PRNG in pure ``jnp`` uint32 arithmetic.

The paper's share-mask randomness ("Random Number", Alg. 1 line 6) is
regenerated here as a *counter-based* stream keyed by
``(key0, key1) = (seed, party/stream id)`` so that

* mask generation is embarrassingly parallel (no sequential state),
* the SPMD backend and the Pallas kernel produce bit-identical masks, and
* a given ``(round, party, share)`` mask can be re-derived for audits.

Reference: Salmon et al., *Parallel random numbers: as easy as 1, 2, 3*
(SC'11).  Constants are the canonical Philox4x32 ones.  This module is
the **oracle**; ``repro/kernels/share_gen`` re-implements the identical
rounds inside a Pallas kernel and is tested for bit-equality against it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .field import mulhilo32

PHILOX_M0 = np.uint32(0xD2511F53)
PHILOX_M1 = np.uint32(0xCD9E8D57)
PHILOX_W0 = np.uint32(0x9E3779B9)
PHILOX_W1 = np.uint32(0xBB67AE85)

_N_ROUNDS = 10


def _round(x0, x1, x2, x3, k0, k1):
    hi0, lo0 = mulhilo32(PHILOX_M0, x0)
    hi1, lo1 = mulhilo32(PHILOX_M1, x2)
    return (hi1 ^ x1 ^ k0, lo1, hi0 ^ x3 ^ k1, lo0)


def philox_4x32_tuple(x0, x1, x2, x3, key0, key1):
    """Tuple-form Philox4x32-10: four uint32 arrays in, four out.

    This is the single source of truth for the rounds — the Pallas
    ``share_gen``/``shamir`` kernels trace exactly this function inside
    their bodies, guaranteeing bit-equality with the oracle.
    """
    k0 = jnp.asarray(key0, dtype=jnp.uint32)
    k1 = jnp.asarray(key1, dtype=jnp.uint32)
    for _ in range(_N_ROUNDS):
        x0, x1, x2, x3 = _round(x0, x1, x2, x3, k0, k1)
        k0 = k0 + PHILOX_W0
        k1 = k1 + PHILOX_W1
    return x0, x1, x2, x3


def philox_4x32(counters, key0, key1):
    """Run Philox4x32-10 over a batch of counters.

    Args:
      counters: uint32 array ``[N, 4]`` (or broadcastable tuple of four
        ``[N]`` arrays) — the per-block counter.
      key0, key1: scalar uint32 key words.

    Returns:
      uint32 array ``[N, 4]`` of random words.
    """
    counters = jnp.asarray(counters, dtype=jnp.uint32)
    x0, x1, x2, x3 = (counters[..., i] for i in range(4))
    y = philox_4x32_tuple(x0, x1, x2, x3, key0, key1)
    return jnp.stack(y, axis=-1)


def tiled_words(rows: int, key0, key1, counter_hi=0, row_base=0,
                layout: str = "tiled"):
    """Lane-tiled uniform words ``[rows, 128]`` — the kernel layout.

    Counter convention (shared with the Pallas kernels): for output
    position ``(r, l)`` the Philox counter word ``x0`` is
    ``(row_base + r) * 32 + l // 4`` and the word used is lane ``l % 4``
    of the block — identical to the flat ``random_bits`` block/word
    mapping for index ``i = 128·r + l``.  ``layout`` places
    ``counter_hi``:

    * ``"tiled"`` — ``x1 = counter_hi`` (the historical kernel stream);
    * ``"flat"``  — ``x2 = counter_hi``, which makes the output equal to
      ``random_bits(128·rows, ..., counter_hi).reshape(rows, 128)``
      bit-for-bit — the stream ``core.additive``/``core.shamir`` mask
      with, so the fused kernels can be bit-identical to those oracles.
    """
    if layout not in ("tiled", "flat"):
        raise ValueError(f"unknown counter layout {layout!r}")
    r = jnp.arange(rows, dtype=jnp.uint32)[:, None]
    lb = jnp.arange(32, dtype=jnp.uint32)[None, :]
    x0 = (r + jnp.asarray(row_base, jnp.uint32)) * jnp.uint32(32) + lb
    hi = jnp.full_like(x0, jnp.asarray(counter_hi, jnp.uint32))
    zero = jnp.zeros_like(x0)
    if layout == "tiled":
        y0, y1, y2, y3 = philox_4x32_tuple(x0, hi, zero, zero, key0, key1)
    else:
        y0, y1, y2, y3 = philox_4x32_tuple(x0, zero, hi, zero, key0, key1)
    return jnp.stack([y0, y1, y2, y3], axis=-1).reshape(rows, 128)


def random_bits(n: int, key0, key1, counter_hi=0, counter_base=0):
    """Generate ``n`` uniform uint32 words from the keyed stream.

    The counter layout is ``(c, 0, counter_hi, 0)`` with
    ``c = counter_base + arange(ceil(n/4))``; ``counter_hi`` is used by
    callers to separate logical sub-streams (e.g. share index) without
    touching the key.
    """
    n_blocks = -(-n // 4)
    c = (jnp.arange(n_blocks, dtype=jnp.uint32)
         + jnp.asarray(counter_base, dtype=jnp.uint32))
    zeros = jnp.zeros_like(c)
    hi = jnp.full_like(c, jnp.asarray(counter_hi, dtype=jnp.uint32))
    counters = jnp.stack([c, zeros, hi, zeros], axis=-1)
    words = philox_4x32(counters, key0, key1)
    return words.reshape(-1)[:n]


def random_bits_like(x, key0, key1, counter_hi=0, counter_base=0):
    """Uniform uint32 words with the shape of ``x``.

    ``counter_base`` offsets into the counter stream in *blocks* of four
    words, exactly as ``random_bits`` — chunked callers that process
    elements ``[off, off+L)`` of a logical vector pass
    ``counter_base=off//4`` (with ``off % 4 == 0``) to draw the same
    words the whole-vector call would have drawn at those positions.
    """
    flat = random_bits(int(np.prod(x.shape)) if x.shape else 1, key0, key1,
                       counter_hi=counter_hi, counter_base=counter_base)
    return flat.reshape(x.shape)


def int_words(v: int) -> tuple[np.uint32, np.uint32]:
    """The ``(lo, hi)`` uint32 words of a Python int, as ``derive_key``
    reads it: jitted callers pass these to keep one compile per shape."""
    v = int(v)
    return np.uint32(v & 0xFFFFFFFF), np.uint32((v >> 32) & 0xFFFFFFFF)


def derive_key(seed, stream):
    """Stateless (key0, key1) derivation from a seed and a stream id.

    A single Philox invocation whitens the pair so related seeds do not
    produce related keys.  ``seed``/``stream`` may be Python ints,
    traced int32/uint32 scalars (traced values use their low 32 bits),
    or explicit ``(lo, hi)`` word pairs — the pair form lets vmapped
    callers keep the high word of a >32-bit stream id (bit-identical to
    passing the same id as a Python int).
    """
    def split(v):
        if isinstance(v, tuple):
            lo, hi = v
            return (jnp.asarray(lo).astype(jnp.uint32),
                    jnp.asarray(hi).astype(jnp.uint32))
        if isinstance(v, (int, np.integer)):
            return split(int_words(v))
        return jnp.asarray(v).astype(jnp.uint32), jnp.uint32(0)

    s_lo, s_hi = split(seed)
    t_lo, t_hi = split(stream)
    c = jnp.stack([jnp.broadcast_to(x, ()) for x in
                   (s_lo, s_hi, t_lo, t_hi)])[None, :]
    w = philox_4x32(c, jnp.uint32(0x243F6A88), jnp.uint32(0x85A308D3))
    return w[0, 0], w[0, 1]


# ---------------------------------------------------------------------------
# numpy oracle (used by kernel tests to triangulate jnp vs pallas vs numpy)
# ---------------------------------------------------------------------------

def np_philox_4x32(counters, key0, key1):
    counters = np.asarray(counters, dtype=np.uint32)
    x = [counters[..., i].astype(np.uint64) for i in range(4)]
    k0 = np.uint64(int(key0))
    k1 = np.uint64(int(key1))
    M0 = np.uint64(0xD2511F53)
    M1 = np.uint64(0xCD9E8D57)
    MASK = np.uint64(0xFFFFFFFF)
    for _ in range(_N_ROUNDS):
        p0 = M0 * x[0]
        p1 = M1 * x[2]
        hi0, lo0 = p0 >> np.uint64(32), p0 & MASK
        hi1, lo1 = p1 >> np.uint64(32), p1 & MASK
        x = [hi1 ^ x[1] ^ k0, lo1, hi0 ^ x[3] ^ k1, lo0]
        k0 = (k0 + np.uint64(0x9E3779B9)) & MASK
        k1 = (k1 + np.uint64(0xBB67AE85)) & MASK
    return np.stack([xi.astype(np.uint32) for xi in x], axis=-1)
