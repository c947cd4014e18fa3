"""Differential tests for the kernel dispatch subsystem (DESIGN.md §7).

Three layers of bit-identity, property-tested over random shapes,
share counts, and round indices (hypothesis, shimmed by conftest when
absent):

1. stream: ``philox.tiled_words(layout="flat")`` == ``random_bits`` —
   the flat counter layout IS the ``core.additive``/``core.shamir``
   mask stream;
2. kernel: interpret-mode Pallas share-gen (flat layout) == the
   additive/Shamir oracles, per party and batched;
3. protocol: ``SecureAggregator`` batch paths give the same bits under
   every dispatch mode, and the default path is pinned to the exact
   pre-dispatch vmap implementation (inlined below as the golden).

Any skip in this module must carry a ``capability:`` reason — the CI
kernels job fails on any other skip.
"""


import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import additive, philox, shamir
from repro.core.aggregation import SecureAggregator
from repro.core.fixed_point import DEFAULT_FIELD, DEFAULT_RING
from repro.kernels import dispatch
from repro.kernels.share_gen import (share_gen, share_gen_batch,
                                     unpad_flat)
from repro.kernels.reconstruct import reconstruct
from repro.kernels.shamir import shamir_share, shamir_share_batch

pytestmark = pytest.mark.kernels


def _require_interpret():
    cap = dispatch.probe()
    if cap == dispatch.CAP_REF_ONLY:
        pytest.skip("capability: pallas interpret mode unavailable on "
                    f"this backend ({cap})")


def _keys_for(seed, ids):
    ks = [np.asarray(philox.derive_key(seed, int(i)),
                     dtype=np.uint32).ravel() for i in ids]
    return np.stack(ks)


# ---------------------------------------------------------------------------
# 1. stream identity
# ---------------------------------------------------------------------------

@settings(max_examples=10)
@given(st.integers(min_value=1, max_value=9), st.integers(0, 2**31 - 1),
       st.integers(0, 63))
def test_tiled_flat_layout_equals_random_bits(rows, seed, hi):
    k0, k1 = philox.derive_key(seed, 1)
    tiled = philox.tiled_words(rows, k0, k1, counter_hi=hi, layout="flat")
    flat = philox.random_bits(rows * 128, k0, k1, counter_hi=hi)
    np.testing.assert_array_equal(np.asarray(tiled).reshape(-1),
                                  np.asarray(flat))


# ---------------------------------------------------------------------------
# 2. kernel-vs-oracle bit identity (interpret mode)
# ---------------------------------------------------------------------------

#: update lengths drawn from a fixed set, so each compiles once per file:
#: below one 1024-element tile (block_rows=8), not a multiple of 128 and
#: a multiple, exactly one tile, a ragged last tile (a multiple of 128
#: and not); the values stay random
_LENGTHS = (1, 100, 128, 1024, 1536, 2000)
_SEEDS = st.integers(min_value=0, max_value=2**32 - 1)


@settings(max_examples=8)
@given(st.sampled_from(_LENGTHS),
       st.integers(min_value=1, max_value=6),
       st.integers(min_value=0, max_value=2**20), _SEEDS)
def test_share_gen_flat_bit_identical_to_additive(d, m, stream, seed):
    _require_interpret()
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    k0, k1 = philox.derive_key(5, stream)
    want = additive.share(DEFAULT_RING.encode(x), m, k0, k1)
    got, dd = share_gen(x, m, k0, k1, DEFAULT_RING, block_rows=8,
                        interpret=True, layout="flat")
    np.testing.assert_array_equal(np.asarray(unpad_flat(got, dd)),
                                  np.asarray(want))


@settings(max_examples=6)
@given(st.sampled_from((1, 100, 128, 1024, 1280, 1500)),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=4), _SEEDS)
def test_shamir_flat_bit_identical_to_oracle(d, m, degree, seed):
    _require_interpret()
    degree = min(degree, m - 1)
    rng = np.random.RandomState(seed)
    x = jnp.asarray((rng.randn(d) * 2).astype(np.float32))
    k0, k1 = philox.derive_key(9, d + m)
    want = shamir.share(DEFAULT_FIELD.encode(x), m, k0, k1, degree=degree)
    got, dd = shamir_share(x, m, k0, k1, DEFAULT_FIELD, degree=degree,
                           block_rows=8, interpret=True, layout="flat")
    np.testing.assert_array_equal(np.asarray(unpad_flat(got, dd)),
                                  np.asarray(want))


@settings(max_examples=6)
@given(st.integers(min_value=1, max_value=5),
       st.sampled_from(_LENGTHS[:3] + (900,)),
       st.integers(min_value=1, max_value=5),
       st.booleans(), _SEEDS)
def test_batched_kernels_bit_identical_per_party(l, d, m, use_shamir,
                                                 seed):
    _require_interpret()
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(l, d).astype(np.float32))
    keys = _keys_for(3, range(l))
    if use_shamir:
        got, dd = shamir_share_batch(xs, m, keys, DEFAULT_FIELD,
                                     block_rows=8, interpret=True)
    else:
        got, dd = share_gen_batch(xs, m, keys, DEFAULT_RING, block_rows=8,
                                  interpret=True)
    for p in range(l):
        k0 = jnp.uint32(keys[p, 0])
        k1 = jnp.uint32(keys[p, 1])
        if use_shamir:
            want = shamir.share(DEFAULT_FIELD.encode(xs[p]), m, k0, k1)
        else:
            want = additive.share(DEFAULT_RING.encode(xs[p]), m, k0, k1)
        np.testing.assert_array_equal(
            np.asarray(unpad_flat(got[p], dd)), np.asarray(want))


@settings(max_examples=8)
@given(st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=500))
def test_reconstruct_kernel_vs_ref_any_n(m, n):
    """Including non-power-of-two n: the decode float sequence matches."""
    _require_interpret()
    rng = np.random.RandomState(m * 1000 + n)
    shares = jnp.asarray(
        rng.randint(0, 2**32, size=(m, 8, 128), dtype=np.uint64)
        .astype(np.uint32))
    got = reconstruct(shares, n, DEFAULT_RING, block_rows=8, interpret=True)
    # ref through the same jitted op wrapper: XLA folds the constant
    # /scale/n pair identically on both paths (eager-vs-jit would
    # differ by 1 ulp for non-power-of-two n; the protocol hot path
    # sidesteps this entirely — see SecureAggregator.reconstruct_mean)
    want = reconstruct(shares, n, DEFAULT_RING, block_rows=8, use_ref=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# 3. SecureAggregator: pre-dispatch golden + cross-mode identity
# ---------------------------------------------------------------------------

def _golden_make_shares_batch(agg, flats, *, seed, party_ids, round_index):
    """The exact pre-dispatch vmap implementation (PR 1), inlined."""
    flats = jnp.asarray(flats, dtype=jnp.float32)
    ids = jnp.asarray(np.asarray(party_ids), dtype=jnp.uint32)
    stream_lo = jnp.uint32((round_index << 24) & 0xFFFFFFFF) | ids
    stream_hi = (round_index << 24) >> 32

    def _one(flat, lo):
        k0, k1 = philox.derive_key(seed, (lo, stream_hi))
        code = agg.encode(flat)
        if agg.scheme == "additive":
            return additive.share(code, agg.m, k0, k1)
        return shamir.share(code, agg.m, k0, k1, degree=agg.shamir_degree)

    return jax.vmap(_one)(flats, stream_lo)


def _golden_aggregate(agg, flats, *, seed, round_index):
    """Pre-dispatch reference epilogue: reconstruct_sum + decode_mean."""
    n = flats.shape[0]
    stacks = _golden_make_shares_batch(agg, flats, seed=seed,
                                       party_ids=np.arange(n),
                                       round_index=round_index)
    member_sums = agg.reduce_party_shares(stacks)
    if agg.scheme == "additive":
        total = additive.reconstruct(member_sums)
    else:
        total = shamir.reconstruct(member_sums)
    return agg.decode_mean(total, n)


@pytest.mark.parametrize("scheme", ["additive", "shamir"])
@pytest.mark.parametrize("round_index", [0, 5, 300])
def test_aggregator_regression_pinned_to_pre_dispatch(scheme, round_index):
    """Default dispatch output is bit-unchanged vs the pre-PR paths."""
    rng = np.random.RandomState(round_index + len(scheme))
    flats = jnp.asarray(rng.randn(5, 641).astype(np.float32))
    agg = SecureAggregator(scheme=scheme, m=3)
    got = agg.make_shares_batch(flats, seed=13, party_ids=np.arange(5),
                                round_index=round_index)
    want = _golden_make_shares_batch(agg, flats, seed=13,
                                     party_ids=np.arange(5),
                                     round_index=round_index)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    mean = agg.aggregate_reference(list(flats), seed=13,
                                   round_index=round_index)
    mean_want = _golden_aggregate(agg, flats, seed=13,
                                  round_index=round_index)
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(mean_want))


@pytest.mark.parametrize("round_index", [7, 300])
@pytest.mark.parametrize("scheme,degree", [("additive", None),
                                           ("shamir", None), ("shamir", 1)])
def test_aggregator_modes_bit_identical(scheme, degree, round_index):
    """ref / interpret dispatch modes produce identical bits end-to-end.

    At round 300 the high stream word is nonzero; n = 5 is no power of
    two, so a reciprocal folded into the mean's division would show."""
    _require_interpret()
    rng = np.random.RandomState(1)
    flats = jnp.asarray(rng.randn(5, 777).astype(np.float32))
    aggs = {mode: SecureAggregator(scheme=scheme, m=3, shamir_degree=degree,
                                   kernel_backend=mode)
            for mode in ("ref", "interpret")}
    outs = {}
    for mode, agg in aggs.items():
        stacks = agg.make_shares_batch(flats, seed=11,
                                       party_ids=np.arange(5),
                                       round_index=round_index)
        sums = agg.reduce_party_shares(stacks)
        outs[mode] = (np.asarray(stacks),
                      np.asarray(agg.reconstruct_mean(sums, 5)))
    np.testing.assert_array_equal(outs["ref"][0], outs["interpret"][0])
    np.testing.assert_array_equal(outs["ref"][1], outs["interpret"][1])
    if scheme == "shamir" and degree == 1:
        sums = aggs["ref"].reduce_party_shares(
            aggs["ref"].make_shares_batch(flats, seed=11,
                                          party_ids=np.arange(5),
                                          round_index=round_index))
        sub = jnp.asarray([0, 2])
        np.testing.assert_array_equal(
            np.asarray(aggs["ref"].reconstruct_mean(sums[sub], 5,
                                                    points=(1, 3))),
            np.asarray(aggs["interpret"].reconstruct_mean(sums[sub], 5,
                                                          points=(1, 3))))


@pytest.mark.parametrize("scheme", ["additive", "shamir"])
def test_kernel_path_compiles_nothing_after_first_round(scheme):
    """Seed, round and chunk offset are operands of the kernel path's
    executables: after round 0 no round (300 has a nonzero high stream
    word) and no new seed compiles an executable."""
    _require_interpret()
    rng = np.random.RandomState(3)
    flats = jnp.asarray(rng.randn(3, 640).astype(np.float32))
    agg = SecureAggregator(scheme=scheme, m=3, kernel_backend="interpret")

    def mean(seed, round_index):
        return agg.aggregate_stream(
            flats, seed=seed, party_ids=np.arange(3),
            round_index=round_index, chunk_elems=256).block_until_ready()

    mean(21, 0)
    compiled = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for seed, round_index in ((21, 1), (21, 300), (22, 1)):
            mean(seed, round_index)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == []


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_additive_reconstruct_points_raises_on_every_backend(mode):
    """points= with additive sharing must raise loudly on the kernel
    path too — silently summing a member-row subset leaves masks
    uncancelled (garbage means)."""
    if mode == "interpret":
        _require_interpret()
    agg = SecureAggregator(scheme="additive", m=3, kernel_backend=mode)
    sums = jnp.zeros((3, 64), jnp.uint32)
    with pytest.raises(ValueError, match="Shamir-only"):
        agg.reconstruct_mean(sums[:2], 4, points=(1, 2))


def test_sum_shares_batch_routes_identically():
    _require_interpret()
    rng = np.random.RandomState(2)
    flats = jnp.asarray(rng.randn(7, 513).astype(np.float32))
    for scheme in ("additive", "shamir"):
        a = SecureAggregator(scheme=scheme, m=3, kernel_backend="ref")
        b = SecureAggregator(scheme=scheme, m=3, kernel_backend="interpret")
        sa = a.sum_shares_batch(flats, seed=4, party_ids=np.arange(7),
                                round_index=2, chunk=3)
        sb = b.sum_shares_batch(flats, seed=4, party_ids=np.arange(7),
                                round_index=2, chunk=3)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


# ---------------------------------------------------------------------------
# dispatch policy unit tests
# ---------------------------------------------------------------------------

def test_decide_ladder_and_env(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    assert dispatch.decide(use_ref=True).mode == "ref"
    assert dispatch.decide(interpret=True).mode == "interpret"
    assert dispatch.decide(interpret=False).mode == "compiled"
    auto = dispatch.decide()
    if dispatch.probe() == dispatch.CAP_TPU:
        assert auto.mode == "compiled"
    elif dispatch.probe() == dispatch.CAP_INTERPRET:
        assert auto.mode == "interpret"
        assert dispatch.decide(hot_path=True).mode == "ref"
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert dispatch.decide(interpret=True).mode == "ref"  # env beats arg
    assert dispatch.decide(hot_path=True, forced="interpret").mode == \
        "interpret"                                       # forced beats env
    # forced="auto" must DEFER to the env escape hatch, not disable it
    assert dispatch.decide(hot_path=True, forced="auto").mode == "ref"
    monkeypatch.setenv(dispatch.ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        dispatch.decide()
    monkeypatch.delenv(dispatch.ENV_VAR)
    with pytest.raises(ValueError):
        dispatch.decide(forced="bogus")


def test_env_escape_hatch_forces_oracle(monkeypatch):
    """REPRO_KERNEL_BACKEND=ref is the forced-oracle escape hatch."""
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    rng = np.random.RandomState(0)
    flats = jnp.asarray(rng.randn(3, 257).astype(np.float32))
    agg = SecureAggregator(m=3)
    got = agg.make_shares_batch(flats, seed=1, party_ids=np.arange(3))
    want = _golden_make_shares_batch(agg, flats, seed=1,
                                     party_ids=np.arange(3), round_index=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_capability_summary_reports_probe():
    s = dispatch.capability_summary()
    assert s["capability"] in (dispatch.CAP_TPU, dispatch.CAP_INTERPRET,
                               dispatch.CAP_REF_ONLY)
    assert s["backend"] == jax.default_backend()
