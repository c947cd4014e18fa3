"""Phase I — MPC committee election (paper Alg. 2).

Every party draws a batch of ``b`` uniform votes in ``[0, n)``; the vote
vectors are summed *under secret sharing* (so nobody learns anyone's
votes), the aggregate is reduced ``mod n``, and the resulting indices
are tallied; the ``m`` highest-scoring parties form the committee.
Because the sum of uniform randoms mod n is uniform as long as at least
one party is honest, no party can bias the outcome.

The paper runs one round with ``b = 10`` ("one round is more than
sufficient"); we keep the re-draw loop with a bounded retry in case
fewer than ``m`` distinct indices appear (possible for tiny ``b``).

The message pattern of the election is the standard P2P additive MPC of
Alg. 1 on a ``b``-vector — the simulation backend routes it through the
same share/exchange machinery so its messages are counted against
Eqs. 3–4, and the SPMD backend lowers it to one tiny ``psum``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import philox
from .additive import share as additive_share


@dataclasses.dataclass(frozen=True)
class ElectionResult:
    committee: tuple[int, ...]   # party indices, sorted by score desc
    rounds: int                  # election rounds used
    tally: np.ndarray            # final per-party vote tally [n]


def draw_votes(n: int, b: int, key0, key1, round_index: int = 0):
    """Party-local uniform votes in ``[0, n)`` as uint32 ``[b]``."""
    bits = philox.random_bits(b, key0, key1, counter_hi=0x5E1EC7 + round_index)
    return bits % jnp.uint32(n)


def share_votes(votes, n: int, key0, key1):
    """Secret-share the vote vector among all ``n`` parties (P2P MPC)."""
    return additive_share(votes, n, key0, key1)


def tally_votes(vote_sum, n: int) -> np.ndarray:
    """Aggregate vote vector -> per-party tally (Alg. 2 lines 22-25)."""
    idx = np.asarray(vote_sum, dtype=np.uint64) % np.uint64(n)
    return np.bincount(idx.astype(np.int64), minlength=n)


def select_committee(tally: np.ndarray, m: int,
                     exclude=(),
                     reputation: dict[int, float] | None = None
                     ) -> list[int]:
    """Top-m parties by tally; deterministic lowest-index tie-break.

    ``exclude``: party ids barred from serving (evicted — blamed by the
    VSS layer or administratively removed); their votes still count
    (Alg. 2's unbiased sum needs every party's randomness) but they can
    never be selected.

    ``reputation``: optional per-party weight multiplying the tally
    (default 1.0) — the per-round re-election scores faulted members
    down without hard-evicting them (DESIGN.md §10).  ``None`` keeps
    the exact integer scoring path, bit-identical to the historical
    election.
    """
    excluded = set(int(i) for i in exclude)
    if reputation is None:
        order = np.lexsort((np.arange(len(tally)), -tally))
        voted = [int(i) for i in order
                 if tally[i] > 0 and i not in excluded]
        return voted[:m]
    # float64 weighted score; ties (incl. weight 0) break on index.
    # every side of the protocol (sim transport, each wire party, the
    # conformance oracle) computes this same sequence, so determinism
    # only needs IEEE float64 — which numpy guarantees cross-process.
    weights = np.array([float(reputation.get(i, 1.0))
                        for i in range(len(tally))])
    score = tally.astype(np.float64) * weights
    order = np.lexsort((np.arange(len(tally)), -score))
    voted = [int(i) for i in order
             if tally[i] > 0 and score[i] > 0.0 and i not in excluded]
    return voted[:m]


@functools.partial(jax.jit, static_argnames="b")
def _voter_draw(seed_words, stream, c, round_index, *, b: int):
    """One voter's ``[b]`` votes, jitted on scalars.

    Compiled once per ``b``: seed, stream, voter count and round are
    run-time values.  A vmap over voters (vector keys) takes XLA's CPU
    compiler ~15 s per voter count instead of ~1 s.
    """
    k0, k1 = philox.derive_key(seed_words, stream)
    return draw_votes(c, b, k0, k1, round_index=round_index)


def elect(n: int, m: int, b: int, seed: int, max_rounds: int = 8,
          exclude=(),
          reputation: dict[int, float] | None = None) -> ElectionResult:
    """Full election as every honest party computes it (deterministic
    given the per-party Philox seeds, which the simulation backend uses
    to cross-check that all parties agree on ``C``).

    ``exclude``/``reputation`` forward to ``select_committee`` — the
    per-round re-election path evicts blamed members and reweights
    faulted ones; defaults are bit-identical to the historical
    single-shot election.
    """
    return elect_among(range(n), m, b, seed, max_rounds=max_rounds,
                       exclude=exclude, reputation=reputation)


def elect_among(ids, m: int, b: int, seed: int, max_rounds: int = 8,
                exclude=(),
                reputation: dict[int, float] | None = None
                ) -> ElectionResult:
    """Alg. 2 over an arbitrary voter set (a sampled cohort).

    ``ids`` are *global* party ids: each voter draws from the same
    Philox stream ``(r << 20) | id`` it would use in a full election,
    votes land in ``[0, c)`` with ``c = len(ids)`` and are tallied over
    positions in ``sorted(ids)``; the winning positions map back to
    global ids.  ``exclude``/``reputation`` stay keyed by global id.
    Bit-identical to :func:`elect` when ``ids == range(n)`` (positions
    coincide with ids), which keeps every existing election — and its
    wire-party/oracle cross-checks — unchanged.
    """
    ids = sorted({int(i) for i in ids})
    c = len(ids)
    excluded = set(int(i) for i in exclude)
    if m > c:
        raise ValueError(f"committee m={m} larger than parties n={c}")
    if c - len(excluded & set(ids)) < m:
        raise ValueError(
            f"cannot elect a committee of {m} from {c} parties with "
            f"{sorted(excluded)} evicted")
    pos_exclude = [p for p, i in enumerate(ids) if i in excluded]
    pos_reputation = None
    if reputation is not None:
        pos_reputation = {p: float(reputation.get(i, 1.0))
                          for p, i in enumerate(ids)}
    committee: list[int] = []
    tally = np.zeros(c, dtype=np.int64)
    streams = np.asarray(ids, dtype=np.uint32)
    seed_words = philox.int_words(seed)
    for r in range(max_rounds):
        # the wraparound uint32 sum is order-independent
        total = np.zeros(b, dtype=np.uint32)
        for stream in np.uint32(r << 20) | streams:
            total += np.asarray(_voter_draw(seed_words, stream,
                                            np.uint32(c), np.uint32(r),
                                            b=b))
        tally = tally + tally_votes(total, c)
        committee = select_committee(tally, m, exclude=pos_exclude,
                                     reputation=pos_reputation)
        if len(committee) == m:
            return ElectionResult(tuple(ids[p] for p in committee),
                                  r + 1, tally)
    raise RuntimeError(
        f"election failed to fill committee of {m} in {max_rounds} rounds "
        f"(n={c}, b={b}) — increase b")
