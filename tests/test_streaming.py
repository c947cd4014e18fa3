"""Streaming chunked aggregation + wired-in top-k compression.

The two contracts of DESIGN.md §8:

* **bit-identity** — the chunked pipeline equals the whole-vector path
  bit-for-bit for every (d, chunk_elems, round_index, scheme), because
  chunk ``c`` consumes exactly the per-party Philox counter range it
  would occupy inside the full vector (hypothesis differential test);
* **convergence** — top-k sparsification with persistent per-party
  error feedback stays within 1.2× of the dense-round loss on the
  paper's SimpleNN task while shrinking upload bytes by ~1/ratio.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import SecureAggregator
from repro.core.compression import CompressionConfig
from repro.core import costmodel
from repro.core.costmodel import CostParams
from repro.data import fault_detection_party, train_test_split
from repro.fl import FedAvgConfig, FLSimulation, run_fedavg
from repro.models import simple_nn


def _flats(l, d, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(l, d).astype(np.float32) * 0.1)


# ---------------------------------------------------------------------------
# bit-identity of the streaming pipeline
# ---------------------------------------------------------------------------

#: (d, chunk_elems): below one chunk, exactly one chunk, whole chunks,
#: ragged last chunks with d a multiple of 128 and not — a fixed set,
#: so each shape compiles once per file while the values stay random
_STREAM_SHAPES = [(1, 128), (100, 256), (256, 256), (768, 256),
                  (640, 384), (2500, 768)]


@settings(max_examples=12)
@given(st.sampled_from(_STREAM_SHAPES),
       st.integers(min_value=0, max_value=2 ** 31 - 1),
       st.integers(min_value=0, max_value=300),
       st.sampled_from(["additive", "shamir"]))
def test_chunked_bitwise_equals_whole_vector(shape, seed, round_index,
                                             scheme):
    """aggregate_stream == sum_shares_batch + reconstruct_mean, exactly."""
    d, chunk_elems = shape
    l = 4
    flats = _flats(l, d, seed=seed)
    ids = np.arange(l) + 1
    agg = SecureAggregator(scheme=scheme, m=3)
    whole = agg.reconstruct_mean(
        agg.sum_shares_batch(flats, seed=11, party_ids=ids,
                             round_index=round_index), l)
    stream = agg.aggregate_stream(flats, seed=11, party_ids=ids,
                                  round_index=round_index,
                                  chunk_elems=chunk_elems, party_chunk=3)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(stream))


@pytest.mark.parametrize("backend", [None, "interpret"])
def test_chunked_share_slices_match_kernel_paths(backend):
    """Chunk c's share stack == the whole-vector stack slice, per
    dispatch mode (oracle vmap and interpret-mode Pallas kernel)."""
    l, d, off = 3, 700, 256
    flats = _flats(l, d, seed=3)
    ids = np.arange(l)
    for scheme in ("additive", "shamir"):
        agg = SecureAggregator(scheme=scheme, m=3, kernel_backend=backend)
        whole = agg.make_shares_batch(flats, seed=5, party_ids=ids,
                                      round_index=9)
        part = agg.make_shares_batch(flats[:, off:off + 256], seed=5,
                                     party_ids=ids, round_index=9,
                                     elem_base=off)
        np.testing.assert_array_equal(np.asarray(whole)[:, :, off:off + 256],
                                      np.asarray(part))


def test_chunked_transport_round_identical_with_committee_dropout():
    """Full TwoPhaseTransport round: chunked == whole, including the
    Shamir sub-threshold (member_rows/points) reconstruction path."""
    n, d = 5, 900
    flats = [jnp.asarray(f) for f in np.asarray(_flats(n, d, seed=1))]
    means = []
    for chunk_elems in (None, 256):
        sim = FLSimulation(n, m=3, scheme="shamir", seed=4,
                           shamir_degree=1, chunk_elems=chunk_elems)
        sim.elect_committee()
        mean, _ = sim.aggregate_two_phase(flats, committee_dropout=[
            sim.committee[0]])
        means.append(np.asarray(mean))
    np.testing.assert_array_equal(means[0], means[1])


def test_stream_with_callable_source_matches_array_source():
    """Lazy block producers (l×d never materialized) give the same bits."""
    l, d = 6, 1111
    flats = _flats(l, d, seed=7)
    ids = np.arange(l)
    agg = SecureAggregator(m=3)

    def source(p_lo, p_hi, e_lo, e_hi):
        return flats[p_lo:p_hi, e_lo:e_hi]

    a = agg.aggregate_stream(flats, seed=2, party_ids=ids,
                             chunk_elems=512, party_chunk=4)
    b = agg.aggregate_stream(source, seed=2, party_ids=ids, d=d,
                             chunk_elems=512, party_chunk=4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_alignment_validated():
    agg = SecureAggregator(m=3)
    flats = _flats(2, 300)
    with pytest.raises(ValueError, match="multiple of 128"):
        agg.aggregate_stream(flats, seed=0, party_ids=[0, 1],
                             chunk_elems=100)
    with pytest.raises(ValueError, match="multiple of 128"):
        agg.make_shares_batch(flats, seed=0, party_ids=[0, 1],
                              elem_base=100)
    with pytest.raises(ValueError, match="requires d="):
        agg.aggregate_stream(lambda *a: None, seed=0, party_ids=[0, 1])


# ---------------------------------------------------------------------------
# unknown-kwarg validation (typos must raise, not silently drop knobs)
# ---------------------------------------------------------------------------

def test_simulation_rejects_unknown_aggregation_kwargs():
    with pytest.raises(TypeError, match="chunk_elems"):
        FLSimulation(4, chunk_elms=256)      # typo -> did-you-mean hint
    with pytest.raises(TypeError, match="compression"):
        FLSimulation(4, compresion=CompressionConfig(enabled=True))


def test_fedavg_config_rejects_typoed_agg_kwargs():
    from repro.deprecation import ReproDeprecationWarning
    cfg = FedAvgConfig(n_parties=2, epochs=1, local_steps=1,
                       agg_kwargs={"chunk_elms": 256})
    # the legacy dict path warns on use (repro.api is the typed front
    # door) but still rejects typos with the did-you-mean hint
    with pytest.warns(ReproDeprecationWarning):
        with pytest.raises(TypeError, match="did you mean 'chunk_elems'"):
            run_fedavg(cfg, {"w": jnp.zeros((2,))},
                       lambda p, b: p, lambda p, e, i: None)
    with pytest.raises(ValueError, match="compress_topk"):
        FedAvgConfig(n_parties=2, compress_topk=1.5)


# ---------------------------------------------------------------------------
# compression wire accounting == sparsified closed forms (Eqs. 2/4/6)
# ---------------------------------------------------------------------------

def test_compressed_counters_match_sparsified_equations():
    n, s, e, ratio = 6, 500, 4, 0.1
    flats = [jnp.asarray(f) for f in np.asarray(_flats(n, s, seed=2))]
    comp = CompressionConfig(enabled=True, top_k_ratio=ratio)
    p = CostParams(n=n, e=e, s=s, m=3, b=10)

    sim = FLSimulation(n, m=3, seed=1, compression=comp)
    sim.elect_committee()
    for _ in range(e):
        sim.aggregate_two_phase(flats)
    got = sim.net.stats("phase1").msg_size + sim.phase2_stats().msg_size
    assert got == costmodel.twophase_msg_size_topk(p, ratio)

    sim2 = FLSimulation(n, m=3, seed=1, compression=comp)
    for _ in range(e):
        sim2.aggregate_p2p(flats)
    assert sim2.net.stats("p2p").msg_size == \
        costmodel.p2p_msg_size_topk(p, ratio)

    # compression compounds with the paper's n->m reduction
    assert costmodel.combined_reduction_factor(p, ratio) > \
        costmodel.reduction_factor(p)


def test_error_feedback_state_persists_across_rounds_and_dropouts():
    n, d = 4, 400
    flats = [jnp.asarray(f) for f in np.asarray(_flats(n, d, seed=5))]
    comp = CompressionConfig(enabled=True, top_k_ratio=0.25)
    sim = FLSimulation(n, m=3, seed=3, compression=comp)
    sim.elect_committee()
    sim.aggregate_two_phase(flats)
    tr = sim.transports["two_phase"]
    assert set(tr._err_state) == set(range(n))
    err_party3 = np.asarray(tr._err_state[3]).copy()
    assert np.abs(err_party3).max() > 0        # residual mass exists
    # party 3 drops: its residual must survive untouched
    sim.aggregate_two_phase(flats[:3], alive={0, 1, 2})
    np.testing.assert_array_equal(np.asarray(tr._err_state[3]), err_party3)


def test_rejected_round_does_not_corrupt_error_feedback():
    """A round the transport refuses (additive scheme + committee-member
    dropout) must leave every party's top-k residual untouched — like
    the wire counters, EF state only advances on accepted rounds."""
    n, d = 4, 300
    flats = [jnp.asarray(f) for f in np.asarray(_flats(n, d, seed=8))]
    comp = CompressionConfig(enabled=True, top_k_ratio=0.2)
    sim = FLSimulation(n, m=3, seed=6, compression=comp)
    sim.elect_committee()
    sim.aggregate_two_phase(flats)
    tr = sim.transports["two_phase"]
    before = {i: np.asarray(tr._err_state[i]).copy() for i in range(n)}
    with pytest.raises(ValueError, match="cannot reconstruct"):
        sim.aggregate_two_phase(flats,
                                committee_dropout=[sim.committee[0]])
    for i in range(n):
        np.testing.assert_array_equal(np.asarray(tr._err_state[i]),
                                      before[i])


# ---------------------------------------------------------------------------
# e2e: top-k + error feedback converges on the SimpleNN task
# ---------------------------------------------------------------------------

def _simple_nn_task(n_parties, seed=0):
    data = [fault_detection_party(400, seed=seed, party=p)
            for p in range(n_parties)]
    splits = [train_test_split(x, y, seed=p) for p, (x, y) in
              enumerate(data)]
    init, fwd = simple_nn.make_model("simple")

    def loss(p, batch):
        x, y = batch
        return simple_nn.nll_loss(fwd(p, x), y)

    @jax.jit
    def step(p, batch):
        g = jax.grad(loss)(p, (jnp.asarray(batch[0]),
                               jnp.asarray(batch[1])))
        return jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    def batches(party, epoch, it):
        (xtr, ytr), _ = splits[party]
        rng = np.random.RandomState(epoch * 10 + it + party)
        idx = rng.choice(len(xtr), 64)
        return xtr[idx], ytr[idx]

    def eval_loss(params):
        vals = [float(loss(params, (jnp.asarray(xt), jnp.asarray(yt))))
                for _, (xt, yt) in splits]
        return float(np.mean(vals))

    return init, step, batches, eval_loss


def test_topk_error_feedback_converges_near_dense():
    """run_fedavg with --compress-topk-style config: final loss within
    1.2x of the dense rounds, at ~1/ratio fewer upload elements."""
    n = 4
    init, step, batches, eval_loss = _simple_nn_task(n)
    params0 = init(jax.random.PRNGKey(0))

    results = {}
    for name, extra in [
        ("dense", {}),
        ("topk", {"compress_topk": 0.1, "chunk_elems": 128}),
    ]:
        cfg = FedAvgConfig(n_parties=n, epochs=6, local_steps=3,
                           committee=3, protocol="two_phase", seed=0,
                           **extra)
        res = run_fedavg(cfg, params0, step, batches)
        results[name] = (eval_loss(res.params), res.msg_size)

    dense_loss, dense_bytes = results["dense"]
    topk_loss, topk_bytes = results["topk"]
    assert topk_loss <= 1.2 * dense_loss, (topk_loss, dense_loss)
    # uploads dominate phase-2 traffic at n=4, m=3; the sparsified
    # rounds must ship measurably fewer elements in total
    assert topk_bytes < 0.8 * dense_bytes, (topk_bytes, dense_bytes)
