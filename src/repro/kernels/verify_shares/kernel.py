"""Pallas TPU kernel: batched Feldman commitment verification.

The malicious-security hot loop (DESIGN.md §10): every committee
member/coordinator verifies ``k`` rows of field elements against the
aggregate commitments before any reconstruction.  Per element that is
31 fixed-base group multiplies (the ``h^s`` ladder) plus a tiny
Horner-in-the-exponent over the ``c`` commitment limb planes — each
group multiply is ~7 16-bit-limb VPU multiplies + Crandall folds, so
the kernel is compute-bound like the Shamir Horner kernel and fuses
the whole check into one pass over the block (the 62 intermediate limb
tensors per element never touch HBM).

The F_q limb arithmetic is traced from ``core.vss`` (the exact jnp
sequences of the oracle), so compiled/interpret/ref are bit-identical
by construction — pinned by ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import vss


def _verify_kernel(rows_ref, commits_ref, ok_ref, *, k: int, c: int,
                   points: tuple[int, ...], unroll: bool):
    c_hi = [commits_ref[j, 0, :, :] for j in range(c)]
    c_lo = [commits_ref[j, 1, :, :] for j in range(c)]
    for i in range(k):
        lhs_hi, lhs_lo = vss.gpow(rows_ref[i, :, :], unroll=unroll)
        acc = (c_hi[c - 1], c_lo[c - 1])
        for j in range(c - 2, -1, -1):
            acc = vss.qpow_scalar(acc, points[i])
            acc = vss.qmul(acc, (c_hi[j], c_lo[j]))
        ok_ref[i, :, :] = ((lhs_hi == acc[0])
                           & (lhs_lo == acc[1])).astype(jnp.uint32)


def verify_shares_pallas(rows, commits, points: tuple[int, ...],
                         block_rows: int = 64, interpret: bool = False):
    """uint32 [k,R,128] rows + [c,2,R,128] commits -> uint32 [k,R,128]."""
    assert rows.ndim == 3 and rows.shape[2] == 128, rows.shape
    k, r, _ = rows.shape
    assert commits.ndim == 4 and commits.shape[1] == 2, commits.shape
    assert commits.shape[2:] == (r, 128), (commits.shape, rows.shape)
    assert r % block_rows == 0
    assert k == len(points)
    c = commits.shape[0]
    # interpret mode lowers the body to XLA's CPU compiler, which takes
    # minutes on the unrolled exponentiation ladder (core.vss.gpow)
    kernel = functools.partial(_verify_kernel, k=k, c=c,
                               points=tuple(int(p) for p in points),
                               unroll=not interpret)
    return pl.pallas_call(
        kernel,
        grid=(r // block_rows,),
        in_specs=[
            pl.BlockSpec((k, block_rows, 128), lambda g: (0, g, 0)),
            pl.BlockSpec((c, 2, block_rows, 128), lambda g: (0, 0, g, 0)),
        ],
        out_specs=pl.BlockSpec((k, block_rows, 128), lambda g: (0, g, 0)),
        out_shape=jax.ShapeDtypeStruct((k, r, 128), jnp.uint32),
        interpret=interpret,
    )(jnp.asarray(rows, jnp.uint32), jnp.asarray(commits, jnp.uint32))
