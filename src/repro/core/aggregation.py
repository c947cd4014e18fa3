"""Pytree-level secure aggregation — the paper's technique as a library.

``SecureAggregator`` is the single entry point both backends use:

* flatten a gradient/weight pytree into one contiguous codeword vector
  (the paper's "parallel mechanism ... on the entire model tensors"),
* encode to fixed point,
* split into shares (additive / Shamir),
* hand the shares to a *transport* (simulation message-passing or SPMD
  collectives) that returns the summed shares,
* reconstruct + decode + divide by the party count -> FedAvg mean.

The aggregator itself is transport-agnostic; the message/wire behaviour
(two-phase vs P2P, committee, dropouts) lives in ``repro/fl``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import additive, philox, shamir
from .fixed_point import FixedPointConfig, DEFAULT_FIELD, DEFAULT_RING

SCHEME_ADDITIVE = "additive"
SCHEME_SHAMIR = "shamir"

#: Default element-chunk size for the streaming aggregation path — ~1M
#: elements keeps the live share stack at
#: ``party_chunk · m · chunk_elems · 4`` bytes regardless of model size.
DEFAULT_CHUNK_ELEMS = 1 << 20

#: Default party-chunk size of the batched share kernels: one launch
#: shares this many parties' updates.
PARTY_CHUNK = 2048

#: Chunk boundaries must align to the Philox counter tiling shared by
#: the oracle (4-word counter blocks) and the Pallas kernels (128-lane
#: rows): 128 covers both, so ``elem_base // 4`` and ``elem_base // 128``
#: are exact for every chunk.
CHUNK_ALIGN = 128


def _check_chunk_elems(chunk_elems: int) -> int:
    chunk_elems = int(chunk_elems)
    if chunk_elems < CHUNK_ALIGN or chunk_elems % CHUNK_ALIGN != 0:
        raise ValueError(
            f"chunk_elems={chunk_elems} must be a positive multiple of "
            f"{CHUNK_ALIGN} (Philox counter-row alignment; see "
            "DESIGN.md §8)")
    return chunk_elems


@dataclasses.dataclass(frozen=True)
class PartySource:
    """``[l, d]`` party updates that are produced block by block.

    ``fn(p_lo, p_hi, e_lo, e_hi)`` returns the ``[p_hi - p_lo,
    e_hi - e_lo]`` block, so an element-chunked round never holds the
    whole ``[l, d]`` matrix (``SecureAggregator.aggregate_stream``).
    """

    fn: object
    shape: tuple[int, int]

    def __call__(self, p_lo, p_hi, e_lo, e_hi):
        return self.fn(p_lo, p_hi, e_lo, e_hi)


@functools.partial(jax.jit, static_argnums=0)
def _party_shares(agg, flat, seed_words, stream, counter_base):
    """One party's ``[m, D]`` share stack on the jnp oracle path."""
    k0, k1 = philox.derive_key(seed_words, stream)
    code = agg.encode(flat)
    if agg.scheme == SCHEME_ADDITIVE:
        return additive.share(code, agg.m, k0, k1, counter_base=counter_base)
    return shamir.share(code, agg.m, k0, k1, degree=agg.shamir_degree,
                        counter_base=counter_base)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kernel_shares(agg, interpret, flats, seed_words, stream_lo, stream_hi,
                   row_base):
    """``[l, D] -> [l, m, D]`` on the kernel path: the ``[l, 2]`` party
    keys and the fused share kernel in one executable.

    Everything that changes per round or per seed (seed words, stream
    words, counter-row offset) is an operand, so one compile serves
    every round of a shape."""
    from repro.kernels.shamir.ops import _shamir_share_batch_jit
    from repro.kernels.share_gen.ops import _share_gen_batch_jit, unpad_flat
    keys = jax.vmap(lambda lo: jnp.stack(
        philox.derive_key(seed_words, (lo, stream_hi))))(stream_lo)
    block_rows = 8 if interpret else 64
    if agg.scheme == SCHEME_ADDITIVE:
        stacks, _ = _share_gen_batch_jit(
            flats, agg.m, keys, agg.fp, 0, block_rows, False, interpret,
            "flat", row_base)
    else:
        stacks, _ = _shamir_share_batch_jit(
            flats, agg.m, keys, agg.fp, agg.shamir_degree, 0, block_rows,
            False, interpret, "flat", row_base)
    return unpad_flat(stacks, flats.shape[-1])


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _kernel_mean(agg, interpret, points, member_sums, n):
    """``[k, D]`` member sums -> ``[D]`` mean on the kernel path, in one
    executable.

    The kernels decode the *sum* (``n=1``: the in-kernel unscale is an
    exact power-of-two multiply) and the mean divides by ``n``, a
    float32 operand: with a constant divisor XLA would fold the two
    divisions into one reciprocal multiply, 1 ulp off ``decode_mean``'s
    float sequence, which the oracle path keeps."""
    from repro.kernels.reconstruct.ops import _reconstruct_jit
    from repro.kernels.shamir.ops import _shamir_reconstruct_jit
    from repro.kernels.share_gen.ops import pad_to_tiles, unpad_flat
    block_rows = 8 if interpret else 64
    tiled, d = pad_to_tiles(member_sums, block_rows)
    if agg.scheme == SCHEME_ADDITIVE:
        out = _reconstruct_jit(tiled, 1, agg.fp, block_rows, False,
                               interpret)
    else:
        out = _shamir_reconstruct_jit(tiled, 1, agg.fp, points, block_rows,
                                      False, interpret)
    return unpad_flat(out, d) / n


def flatten_pytree(tree):
    """Pytree of float arrays -> (flat float32 vector, unflatten fn)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate(
        [jnp.ravel(l).astype(jnp.float32) for l in leaves]) if leaves else \
        jnp.zeros((0,), jnp.float32)

    def unflatten(vec):
        out = []
        off = 0
        for shape, size in zip(shapes, sizes):
            out.append(jnp.reshape(vec[off:off + size], shape))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unflatten


@dataclasses.dataclass(frozen=True)
class SecureAggregator:
    """Scheme + codec bundle; stateless and jit-friendly.

    Attributes:
      scheme: ``"additive"`` or ``"shamir"``.
      m: number of shares each party produces (committee size; equals n
        in P2P mode).
      fp: fixed-point codec config (algebra must match the scheme).
      shamir_degree: polynomial degree (default m-1, paper's choice).
      kernel_backend: per-object override of the kernel dispatch mode
        for the batch paths (``auto`` | ``compiled`` | ``interpret`` |
        ``ref``; default ``None`` = dispatch policy, which is compiled
        on TPU and the jnp oracle elsewhere — see
        ``kernels.dispatch.decide(hot_path=True)``).  Every mode is
        bit-identical (``tests/test_kernel_dispatch.py``).
    """

    scheme: str = SCHEME_ADDITIVE
    m: int = 3
    fp: FixedPointConfig | None = None
    shamir_degree: int | None = None
    kernel_backend: str | None = None

    def __post_init__(self):
        if self.scheme not in (SCHEME_ADDITIVE, SCHEME_SHAMIR):
            raise ValueError(f"unknown scheme {self.scheme!r}")
        if self.fp is None:
            object.__setattr__(
                self, "fp",
                DEFAULT_RING if self.scheme == SCHEME_ADDITIVE
                else DEFAULT_FIELD)
        want = "ring" if self.scheme == SCHEME_ADDITIVE else "field"
        if self.fp.algebra != want:
            raise ValueError(
                f"scheme {self.scheme} needs {want} codec, got "
                f"{self.fp.algebra}")

    # -- party side -----------------------------------------------------

    def encode(self, flat_float):
        return self.fp.encode(flat_float)

    def make_shares(self, flat_float, *, seed: int, party: int,
                    round_index: int = 0):
        """Encode + split one party's flat update into ``[m, D]`` shares."""
        code = self.encode(flat_float)
        k0, k1 = philox.derive_key(seed, (round_index << 24) | party)
        if self.scheme == SCHEME_ADDITIVE:
            return additive.share(code, self.m, k0, k1)
        return shamir.share(code, self.m, k0, k1,
                            degree=self.shamir_degree)

    def make_shares_batch(self, flats, *, seed: int, party_ids,
                          round_index: int = 0, elem_base: int = 0):
        """All parties' share stacks: ``[l, D] -> [l, m, D]``.

        Bit-identical to stacking per-party ``make_shares`` calls for
        every ``round_index``: party ids stay below 2**24, so the low
        stream word is ``((round_index << 24) | party) & 0xFFFFFFFF``
        and the high word ``round_index >> 8`` is party-independent —
        both are fed to ``derive_key`` exactly as the Python-int path
        of ``make_shares`` derives them.

        ``elem_base``: element offset of this chunk inside the logical
        whole-vector codeword (multiple of ``CHUNK_ALIGN``).  Chunk
        ``c`` then consumes exactly the Philox counter range it would
        occupy inside the full vector — the streaming invariant — so
        ``make_shares_batch(full)[..., off:off+L]`` equals
        ``make_shares_batch(full[:, off:off+L], elem_base=off)``
        bit-for-bit on every dispatch path.

        Routed through ``kernels.dispatch``: the jnp-oracle vmap, the
        interpret-mode Pallas kernel, and the compiled kernel all
        produce the same bits (the kernels mask with the ``"flat"``
        Philox counter layout — exactly ``additive``/``shamir`` streams).
        """
        from repro.kernels import dispatch
        flats = jnp.asarray(flats, dtype=jnp.float32)
        stream_lo = (np.uint32((round_index << 24) & 0xFFFFFFFF)
                     | np.asarray(party_ids, dtype=np.uint32))
        stream_hi = np.uint32((round_index << 24) >> 32)
        seed_words = philox.int_words(seed)
        elem_base = int(elem_base)
        if elem_base % CHUNK_ALIGN != 0 or elem_base < 0:
            raise ValueError(
                f"elem_base={elem_base} must be a non-negative multiple "
                f"of {CHUNK_ALIGN} (counter-row alignment)")

        dec = dispatch.decide(hot_path=True, forced=self.kernel_backend)
        if not dec.use_ref:
            return _kernel_shares(self, dec.interpret, flats, seed_words,
                                  stream_lo, stream_hi,
                                  np.uint32(elem_base // 128))

        # one jitted call per party: an eager vmap retraces ~2,000 ops
        # per call, and with the element-wide masks jitted over parties
        # (vector keys) XLA's CPU backend runs their Philox rounds
        # ~1,000x slower than per party.  The kernel path above vectorises
        # only the l key blocks, which is cheap on every backend.
        counter_base = np.uint32(elem_base // 4)
        return jnp.stack([
            _party_shares(self, flat, seed_words, (lo, stream_hi),
                          counter_base)
            for flat, lo in zip(np.asarray(flats), stream_lo)])

    def sum_shares_batch(self, flats, *, seed: int, party_ids,
                         round_index: int = 0, chunk: int = PARTY_CHUNK,
                         elem_base: int = 0):
        """Streaming share-stack sum: ``[l, D] -> [m, D]`` member sums.

        Generates shares in party chunks of ``chunk`` and accumulates the
        ring/field sum on the fly, so peak memory is ``O(chunk·m·D)``
        instead of ``O(l·m·D)`` — this is what makes l = 10,000-party
        rounds feasible.  The modular sums are order-independent, so the
        result is bit-identical to ``reduce_party_shares`` over the full
        ``make_shares_batch`` stack.  ``elem_base`` forwards the
        element-chunk offset (see ``make_shares_batch``).

        ``flats`` may also be a callable ``(p_lo, p_hi) -> [p, D]``
        block producer — ``aggregate_stream`` uses this so the party
        loop (and its modular accumulator) lives exactly once, here.
        """
        ids = np.asarray(party_ids)
        l = int(ids.shape[0])
        if callable(flats):
            get = flats
        else:
            flats = jnp.asarray(flats, dtype=jnp.float32)
            if flats.shape[0] != l:
                raise ValueError(
                    f"{flats.shape[0]} updates but {l} party ids")

            def get(lo, hi):
                return flats[lo:hi]

        acc = None
        for off in range(0, l, chunk):
            hi = min(off + chunk, l)
            block = jnp.asarray(get(off, hi), dtype=jnp.float32)
            if block.ndim != 2 or block.shape[0] != hi - off:
                raise ValueError(
                    f"party block source returned {block.shape}, "
                    f"expected ({hi - off}, D)")
            stacks = self.make_shares_batch(
                block, seed=seed,
                party_ids=ids[off:hi], round_index=round_index,
                elem_base=elem_base)
            part = self.reduce_party_shares(stacks)
            if acc is None:
                acc = part
            elif self.scheme == SCHEME_ADDITIVE:
                acc = acc + part
            else:
                from .field import fadd
                acc = fadd(acc, part)
        return acc

    # -- streaming chunked pipeline (share -> sum -> reconstruct) ---------

    def aggregate_stream(self, flats, *, seed: int, party_ids,
                         round_index: int = 0,
                         chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                         party_chunk: int = PARTY_CHUNK, d: int | None = None,
                         member_rows=None,
                         points: tuple[int, ...] | None = None,
                         n: int | None = None):
        """Streaming chunked secure aggregation: ``[l, D] -> [D]`` mean.

        Splits the flattened codeword into element chunks of
        ``chunk_elems`` and streams each chunk through
        ``sum_shares_batch -> reconstruct_mean`` (which itself streams
        share generation in party chunks),
        so peak live memory is ``O(party_chunk · m · chunk_elems)``
        instead of ``O(party_chunk · m · D)``.  Bit-identical to the
        whole-vector path by construction: chunk ``c`` consumes exactly
        the per-party Philox counter range it would inside the full
        vector (``elem_base`` plumbing), modular share sums are
        order-independent, and decode is element-wise — pinned by the
        hypothesis differential test in ``tests/test_streaming.py``.

        Args:
          flats: ``[l, D]`` array of per-party flat updates, OR a
            callable ``source(p_lo, p_hi, e_lo, e_hi) -> [p, e]`` block
            producer (lazy sources let ``l·D`` exceed RAM; requires
            ``d`` and an explicit ``party_ids``).
          party_ids: original ids of the ``l`` live parties.
          chunk_elems: element-chunk size (positive multiple of 128).
          party_chunk: party-chunk size of the inner share-sum stream.
          d: codeword length (required for callable ``flats``).
          member_rows: optional index array selecting the live committee
            member rows of each chunk's ``[m, chunk]`` sums before
            reconstruction (Shamir sub-threshold dropout path).
          points: Shamir evaluation points matching ``member_rows``.
          n: divisor of the reconstructed mean (default ``l``).

        Returns:
          float32 ``[D]`` — the FedAvg mean of the ``l`` updates.
        """
        chunk_elems = _check_chunk_elems(chunk_elems)
        ids = np.asarray(party_ids)
        l = int(ids.shape[0])
        if callable(flats):
            if d is None:
                raise ValueError("callable flats requires d=")
            source = flats
        else:
            flats = jnp.asarray(flats, dtype=jnp.float32)
            if flats.shape[0] != l:
                raise ValueError(
                    f"{flats.shape[0]} updates but {l} party ids")
            if d is None:
                d = int(flats.shape[1])

            def source(p_lo, p_hi, e_lo, e_hi):
                return flats[p_lo:p_hi, e_lo:e_hi]

        n = l if n is None else int(n)
        out = []
        for e_lo in range(0, d, chunk_elems):
            e_hi = min(e_lo + chunk_elems, d)

            def col_block(p_lo, p_hi, e_lo=e_lo, e_hi=e_hi):
                block = jnp.asarray(source(p_lo, p_hi, e_lo, e_hi),
                                    dtype=jnp.float32)
                if block.shape != (p_hi - p_lo, e_hi - e_lo):
                    raise ValueError(
                        f"source returned {block.shape}, expected "
                        f"{(p_hi - p_lo, e_hi - e_lo)}")
                return block

            acc = self.sum_shares_batch(
                col_block, seed=seed, party_ids=ids,
                round_index=round_index, chunk=party_chunk,
                elem_base=e_lo)
            if member_rows is not None:
                acc = acc[jnp.asarray(member_rows)]
            out.append(self.reconstruct_mean(acc, n, points=points))
        return out[0] if len(out) == 1 else jnp.concatenate(out)

    # -- committee / reconstruction side ---------------------------------

    def reduce_party_shares(self, stacked):
        """Sum the per-party share stacks (``[n, m, D] -> [m, D]``).

        This is the committee members' *local* aggregation (Alg. 3 l.15):
        pure ring/field addition thanks to additive homomorphism.
        """
        stacked = jnp.asarray(stacked, dtype=jnp.uint32)
        if self.scheme == SCHEME_ADDITIVE:
            return jnp.sum(stacked, axis=0, dtype=jnp.uint32)
        from .field import fsum
        return fsum(stacked, axis=0)

    def reconstruct_sum(self, member_sums, points: tuple[int, ...] | None
                        = None):
        """Combine committee members' sums (``[k, D] -> [D]`` codewords).

        ``points``: the Shamir evaluation points the ``k`` rows sit at
        (default the canonical ``1..k``).  Passing a strict subset of the
        committee's points enables sub-threshold reconstruction after
        member dropouts — only valid for the Shamir scheme, and only
        when ``k >= degree + 1``.
        """
        if self.scheme == SCHEME_ADDITIVE:
            if points is not None:
                raise ValueError(
                    "additive reconstruction needs all m shares; "
                    "points= is a Shamir-only argument")
            return additive.reconstruct(member_sums)
        return shamir.reconstruct(member_sums, points=points)

    def decode_mean(self, code_sum, n: int):
        return self.fp.decode_mean(code_sum, n)

    def reconstruct_mean(self, member_sums, n: int,
                         points: tuple[int, ...] | None = None):
        """Fused reconstruct + decode + 1/n: ``[k, D] -> [D]`` floats.

        The transport epilogue (Alg. 1 l.13–20 / Alg. 3 l.20–22),
        routed through ``kernels.dispatch``: ``kernels/reconstruct``
        (ring) or ``kernels/shamir`` (field Lagrange) when the kernel
        path is selected, the exact pre-dispatch oracle sequence
        (``reconstruct_sum`` + ``decode_mean``) otherwise.  All modes
        are bit-identical — the kernels decode with the same float
        sequence as ``decode_mean`` (exact power-of-two unscale, then
        one division by ``n``).
        """
        from repro.kernels import dispatch
        if points is not None and self.scheme == SCHEME_ADDITIVE:
            # validated on EVERY dispatch path: the kernel branch would
            # otherwise silently sum a subset of member rows (unmatched
            # masks don't cancel) where the oracle raises
            raise ValueError(
                "additive reconstruction needs all m shares; "
                "points= is a Shamir-only argument")
        member_sums = jnp.asarray(member_sums, dtype=jnp.uint32)
        dec = dispatch.decide(hot_path=True, forced=self.kernel_backend)
        if dec.use_ref:
            return self.decode_mean(
                self.reconstruct_sum(member_sums, points), n)
        # n enters as an operand, never a constant (see _kernel_mean)
        return _kernel_mean(self, dec.interpret, points, member_sums,
                            np.float32(n))

    # -- one-shot reference path (no transport; used by tests) -----------

    def aggregate_reference(self, flats, *, seed: int, round_index: int = 0):
        """Share->sum->reconstruct->mean for a list of flat updates."""
        n = len(flats)
        self.fp.validate_for_parties(n)
        member_sums = self.sum_shares_batch(
            jnp.stack([jnp.asarray(f) for f in flats]), seed=seed,
            party_ids=np.arange(n), round_index=round_index)
        return self.reconstruct_mean(member_sums, n)


def secure_mean_pytrees(trees, agg: SecureAggregator, *, seed: int,
                        round_index: int = 0):
    """Convenience: securely average a list of pytrees (reference path)."""
    flats_unf = [flatten_pytree(t) for t in trees]
    flats = [f for f, _ in flats_unf]
    unflatten = flats_unf[0][1]
    mean = agg.aggregate_reference(flats, seed=seed, round_index=round_index)
    return unflatten(mean)
