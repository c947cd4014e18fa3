"""Plain reference of one secure-aggregation round: the exact mean.

The round's answer is the mean of the ``n`` parties' updates.  The
updates lie on a 2^-22 grid, so ``x * 2^22`` are integers and their sum
over the parties is exact in int32 (|sum| < n * 2^22); the program's mean
times ``n * 2^22`` is compared with it in the same integer units.  The
additive scheme with a Q15.16 codec guarantees a gap of at most half a
codec step, 2^-17: each party's encoding rounds by at most that, the
share masks cancel exactly in Z_2^32, and decoding the sum is exact.

The wire counters follow the paper's closed forms: Phase I (Eqs. 3-4)
sends ``2n(n-1)`` messages of ``b`` elements per election round; Phase II
(Eqs. 5-6) sends ``n*m + (m-1) + n`` messages of ``s`` elements per
aggregation round.

Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GRID = 2.0 ** 22


@jax.jit
def _chunk_gap(out, x):
    """Largest |mean - exact mean| of one chunk, in units of 2^-22 / n."""
    n = x.shape[0]
    exact = jnp.sum(jnp.round(x * GRID).astype(jnp.int32), axis=0)
    got = jnp.round(out * (n * GRID)).astype(jnp.int32)
    return jnp.max(jnp.abs(got - exact))


def mean_gap(out, blocks, chunk: int, d: int) -> float:
    """Largest |out - exact mean| over a round; ``blocks[c]`` holds the
    ``[n, chunk]`` updates of element chunk ``c`` (the last one cut to
    ``d``)."""
    n = blocks[0].shape[0]
    worst = 0
    for c, blk in enumerate(blocks):
        lo, hi = c * chunk, min((c + 1) * chunk, d)
        worst = max(worst, int(_chunk_gap(out[lo:hi], blk[:, :hi - lo])))
    return worst / (n * GRID)


def stand_in(blocks, prev_blocks, what: str):
    """A round's mean as a faulty or lower-precision program would give.

    ``control``: the mean of the updates rounded to bfloat16 (float32
    sums).  ``half_batch``: the mean over the first half of the parties.
    ``altered``: the exact mean with one element off by 1.  ``stale``:
    the previous round's mean, returned again.
    """
    means = []
    for blk, prev in zip(blocks, prev_blocks):
        if what == "control":
            m = jnp.mean(blk.astype(jnp.bfloat16).astype(jnp.float32),
                         axis=0)
        elif what == "half_batch":
            m = jnp.mean(blk[:blk.shape[0] // 2], axis=0)
        elif what == "stale":
            m = jnp.mean(prev, axis=0)
        elif what == "altered":
            m = jnp.mean(blk, axis=0)
        else:
            raise ValueError(f"unknown stand-in {what!r}")
        means.append(m)
    out = jnp.concatenate(means)
    if what == "altered":
        out = out.at[0].add(1.0)
    return out


def counter_mismatches(counted: dict, *, n: int, m: int, s: int, b: int,
                       rounds: int) -> int:
    """How many wire counters depart from Eqs. 3-6 after ``rounds``
    aggregation rounds under one election (of any number of vote rounds).
    """
    wrong = 0
    num1, size1 = counted["phase1"]
    per_vote = 2 * n * (n - 1)
    wrong += not (num1 > 0 and num1 % per_vote == 0 and size1 == num1 * b)
    legs = {"phase2_upload": n * m, "phase2_exchange": m - 1,
            "phase2_broadcast": n}
    for phase, per_round in legs.items():
        num, size = counted[phase]
        wrong += num != per_round * rounds
        wrong += size != per_round * rounds * s
    return wrong
