"""Adversarial scenario harness (DESIGN.md §11, ``repro.fl.scenarios``).

Four layers:

* harness unit tests — seeded schedules, the Eq. 3–6 counter mirror
  tied back to the ``costmodel`` closed forms, record schema;
* property tests (hypothesis, shim-compatible) — ``dirichlet_partition``
  invariants and the dealer-blame semantics of ``resolve_outcome``;
* a golden pin of the committed ``BENCH_scenarios.json`` — schema and
  coverage guarantees CI's ``scenarios`` job relies on;
* sim-vs-wire differentials (``-m net``) — composed scenarios must
  produce identical outcomes, bans, counters and final loss on the
  in-process transport and the real multi-process deployment.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import committee as committee_mod
from repro.core import costmodel
from repro.data import dirichlet_partition
from repro.fl.faults import RoundOutcome, resolve_outcome
from repro.fl.scenarios import (ChurnConfig, DealerConfig, ScenarioConfig,
                                StragglerConfig, churn_schedule,
                                expected_counters, run_scenario,
                                straggler_latencies)
from repro.fl.simulation import FLSimulation, UnknownPartyError

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

#: small-but-real training shape shared by the execution tests
_FAST = dict(epochs=2, local_steps=1, samples_per_party=60)


# ---------------------------------------------------------------------------
# seeded schedules
# ---------------------------------------------------------------------------

def test_churn_schedule_deterministic_and_floored():
    cfg = ChurnConfig(leave_prob=0.9, rejoin_prob=0.2, min_parties=2,
                      seed=11)
    a = churn_schedule(6, 8, cfg)
    b = churn_schedule(6, 8, cfg)
    assert a == b, "churn schedule must be a pure function of the seed"
    assert a[0] == frozenset(range(6)), "epoch 0 starts with everyone"
    assert all(len(m) >= 2 for m in a), "min_parties floor violated"
    # a 0.9 leave probability must actually shed parties
    assert any(len(m) < 6 for m in a)


def test_churn_schedule_rejoins():
    cfg = ChurnConfig(leave_prob=0.6, rejoin_prob=1.0, min_parties=1,
                      seed=5)
    sched = churn_schedule(4, 10, cfg)
    rejoined = any(p in sched[e + 1]
                   for e in range(len(sched) - 1)
                   for p in range(4)
                   if p not in sched[e])
    assert rejoined, "rejoin_prob=1.0 must bring a departed party back"


def test_straggler_latencies_deterministic_lognormal():
    cfg = StragglerConfig(median_s=0.3, sigma=1.2, seed=7)
    lat = straggler_latencies(4, cfg)
    assert lat == straggler_latencies(4, cfg)
    assert set(lat) == set(range(4))
    assert all(v > 0 for v in lat.values())


# ---------------------------------------------------------------------------
# Eq. 3-6 counter mirror vs the costmodel closed forms
# ---------------------------------------------------------------------------

def test_expected_counters_reduce_to_closed_forms():
    """Full participation, no blame: the generalized mirror must equal
    the paper's Eqs. 3-6 (single election) exactly."""
    n, m, b, d, epochs = 4, 3, 10, 244, 3
    scn = ScenarioConfig(name="x", n=n, m=m, epochs=epochs,
                         norm_bound=8.0)
    outcomes = [RoundOutcome(alive=set(range(n)), dropped=set(),
                             straggled=set()) for _ in range(epochs)]
    got = expected_counters(scn, d, outcomes)

    p = costmodel.CostParams(n=n, e=epochs, s=d, m=m, b=b)
    rounds = committee_mod.elect(n, m, b, scn.seed).rounds
    assert got["phase1"] == (rounds * costmodel.phase1_msg_num(
        costmodel.CostParams(n=n, e=1, s=d, m=m, b=b)),
        rounds * costmodel.phase1_msg_size(
            costmodel.CostParams(n=n, e=1, s=d, m=m, b=b)))
    # Eq. 5/6 split into the harness's phases: n·m uploads + (m-1)
    # exchanges + n broadcasts per epoch
    assert (got["phase2_upload"][0] + got["phase2_exchange"][0]
            + got["phase2_broadcast"][0]) == costmodel.phase2_msg_num(p)
    assert (got["phase2_upload"][1] + got["phase2_exchange"][1]
            + got["phase2_broadcast"][1]) == costmodel.phase2_msg_size(p)
    assert got["phase2_commit"] == (
        costmodel.phase2_commit_msg_num(p),
        costmodel.phase2_commit_msg_size(p, scn.shamir_degree))
    assert got["phase2_audit"] == (costmodel.phase2_audit_msg_num(p),
                                   costmodel.phase2_audit_msg_size(p))


def test_expected_counters_reelects_after_blame():
    """A blamed dealer triggers a post-round re-election with the
    offender excluded — phase1 must accrue a second election's
    messages and the later epochs shrink to the surviving dealers."""
    n, m, d = 4, 3, 244
    scn = ScenarioConfig(name="x", n=n, m=m, epochs=2, norm_bound=8.0)
    outcomes = [
        RoundOutcome(alive={0, 1, 2}, dropped=set(), straggled=set(),
                     blamed_dealers={3}),
        RoundOutcome(alive={0, 1, 2}, dropped=set(), straggled=set()),
    ]
    got = expected_counters(scn, d, outcomes)
    r0 = committee_mod.elect(n, m, scn.vote_batch, scn.seed).rounds
    r1 = committee_mod.elect(n, m, scn.vote_batch, scn.seed + 1,
                             exclude={3}, reputation={3: 0.0}).rounds
    assert got["phase1"][0] == (r0 + r1) * 2 * n * (n - 1)
    # epoch 0 still counts the poisoning dealer's upload (l=4); epoch 1
    # runs without it (l=3)
    assert got["phase2_upload"][0] == 4 * m + 3 * m


# ---------------------------------------------------------------------------
# execution records (sim backend)
# ---------------------------------------------------------------------------

def test_run_scenario_record_schema_and_counter_match():
    rec = run_scenario(ScenarioConfig(name="unit_honest", **_FAST))
    for key in ("schema_version", "name", "backend", "final_accuracy",
                "final_loss", "wall_s", "round_wall_s", "banned",
                "outcomes", "counters", "counters_expected",
                "counters_match", "aborted"):
        assert key in rec, f"record missing {key}"
    assert rec["counters_match"], (rec["counters"],
                                   rec["counters_expected"])
    assert rec["aborted"] is False and rec["banned"] == []
    assert len(rec["outcomes"]) == _FAST["epochs"]


def test_run_scenario_dealer_blamed_banned_and_model_survives():
    rec = run_scenario(ScenarioConfig(
        name="unit_poison", epochs=3, local_steps=1,
        samples_per_party=60, norm_bound=8.0, honest_twin=True,
        dealers=(DealerConfig(party=3, mode="scale", round_index=1),)))
    assert rec["banned"] == [3]
    assert rec["outcomes"][1]["blamed_dealers"] == [3]
    assert all(3 not in o["alive"] for o in rec["outcomes"][1:])
    assert rec["counters_match"], (rec["counters"],
                                   rec["counters_expected"])
    assert rec["loss_ratio_vs_honest"] <= 1.2, \
        "blame-and-continue must not wreck the model"


def test_run_scenario_malformed_dealer_aborts():
    scn = ScenarioConfig(
        name="unit_malformed", epochs=2, local_steps=1,
        samples_per_party=60, norm_bound=8.0, expect_abort=True,
        dealers=(DealerConfig(party=2, mode="malformed",
                              round_index=1),))
    rec = run_scenario(scn)
    assert rec["aborted"] is True
    assert "dealer share verification failed" in rec["error"]
    # without expect_abort the same scenario must raise loudly
    import dataclasses
    with pytest.raises(ValueError,
                       match="dealer share verification failed"):
        run_scenario(dataclasses.replace(scn, expect_abort=False))


def test_scenario_config_validation():
    with pytest.raises(ValueError, match="not in"):
        DealerConfig(party=0, mode="nonsense")
    with pytest.raises(ValueError, match="outside"):
        ScenarioConfig(name="x", n=3,
                       dealers=(DealerConfig(party=7),))
    with pytest.raises(ValueError, match="sim|wire"):
        ScenarioConfig(name="x", backend="carrier-pigeon")


# ---------------------------------------------------------------------------
# satellite: typed did-you-mean on unregistered party ids
# ---------------------------------------------------------------------------

def test_aggregate_unknown_party_id_typed_error():
    sim = FLSimulation(4, scheme="additive")
    flats = np.zeros((4, 8), dtype=np.float32)
    with pytest.raises(UnknownPartyError,
                       match=r"9 \(did you mean 3\?\)"):
        sim.aggregate("two_phase", flats, party_ids=[0, 1, 2, 9])
    # UnknownPartyError subclasses ValueError: pre-existing callers
    # that caught ValueError keep working
    assert issubclass(UnknownPartyError, ValueError)


# ---------------------------------------------------------------------------
# hypothesis properties: dirichlet_partition
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=2, max_value=6),
       st.sampled_from([10, 50, 100, 500]))
def test_dirichlet_partition_is_a_label_partition(seed, n_parties,
                                                  alpha_pct):
    """Every sample index lands in exactly one party, at any alpha."""
    labels = np.random.RandomState(seed).randint(0, 3, size=120)
    parts = dirichlet_partition(labels, n_parties,
                                alpha=alpha_pct / 100.0, seed=seed)
    assert len(parts) == n_parties
    flat = np.sort(np.concatenate([np.asarray(p, dtype=np.int64)
                                   for p in parts]))
    assert np.array_equal(flat, np.arange(len(labels))), \
        "partition must cover every index exactly once"


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=2, max_value=5))
def test_dirichlet_partition_seed_deterministic(seed, n_parties):
    labels = np.random.RandomState(seed ^ 0xABCD).randint(0, 2, size=80)
    a = dirichlet_partition(labels, n_parties, alpha=0.3, seed=seed)
    b = dirichlet_partition(labels, n_parties, alpha=0.3, seed=seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=2, max_value=4))
def test_dirichlet_partition_large_alpha_no_empty_party(seed, n_parties):
    """alpha -> inf approaches a uniform split: with plenty of samples
    per party no shard may come back empty."""
    labels = np.random.RandomState(seed).randint(0, 2, size=40 * n_parties)
    parts = dirichlet_partition(labels, n_parties, alpha=100.0,
                                seed=seed)
    assert all(len(p) > 0 for p in parts)


# ---------------------------------------------------------------------------
# hypothesis properties: dealer blame in resolve_outcome
# ---------------------------------------------------------------------------

@settings(max_examples=40)
@given(st.integers(min_value=2, max_value=8),
       st.integers(min_value=0, max_value=2**31 - 1),
       st.integers(min_value=0, max_value=2**31 - 1),
       st.booleans())
def test_resolve_outcome_dealer_blame_exclusion(n, blame_seed,
                                                drop_seed,
                                                with_committee):
    """Blamed dealers are out of the round like dropouts, are never
    resurrected into the quorum, and always surface in
    ``blamed_dealers`` — regardless of overlapping fault sets."""
    members = set(range(n))
    rng = np.random.RandomState(blame_seed)
    blamed_dealers = {i for i in members if rng.rand() < 0.4}
    if blamed_dealers == members:
        blamed_dealers.discard(min(members))  # all-blamed tested below
    rng2 = np.random.RandomState(drop_seed)
    dropped = {i for i in members if rng2.rand() < 0.3}
    # an honest committee, as the driver guarantees by re-electing
    # with the blamed parties excluded before the next round
    committee = (sorted(members - blamed_dealers)[:2]
                 if with_committee else None)
    out = resolve_outcome(
        members, dropped, set(), committee=committee,
        reconstruct_threshold=(len(committee) if with_committee
                               else None),
        blamed_dealers=blamed_dealers)
    assert out.blamed_dealers == blamed_dealers
    assert not (out.alive & blamed_dealers), \
        "a blamed dealer must never re-enter the live set"
    assert not (out.dropped & blamed_dealers), \
        "blame wins over dropout in the reporting"
    assert out.alive, "quorum floor must keep an honest survivor"


def test_resolve_outcome_all_blamed_fails_loudly():
    with pytest.raises(ValueError, match="no honest party"):
        resolve_outcome({0, 1, 2}, set(), set(),
                        blamed_dealers={0, 1, 2})


def test_resolve_outcome_blamed_member_precedence_over_dealer():
    """A party in both blame sets reports as a tampering member (the
    harsher verdict); the sets never overlap in the outcome."""
    out = resolve_outcome({0, 1, 2, 3}, set(), set(), blamed={1},
                          blamed_dealers={1, 2})
    assert out.blamed == {1}
    assert out.blamed_dealers == {2}


# ---------------------------------------------------------------------------
# golden pin: committed BENCH_scenarios.json
# ---------------------------------------------------------------------------

def _load_bench():
    path = REPO_ROOT / "BENCH_scenarios.json"
    assert path.exists(), \
        "BENCH_scenarios.json must be committed (benchmarks/scenarios.py)"
    with open(path) as f:
        return json.load(f)


def test_bench_scenarios_schema_and_coverage():
    bench = _load_bench()
    assert bench["schema_version"] == 1
    assert bench["generated_by"] == "benchmarks/scenarios.py"
    assert bench["calib_wall_s"] > 0
    recs = bench["scenarios"]
    assert len(recs) >= 6, "the battery pins at least six scenarios"
    by_name = {r["name"]: r for r in recs}
    assert len(by_name) == len(recs), "scenario names must be unique"

    # stressor coverage: churn, >=2 non-IID alphas, stragglers,
    # poisoned + malformed dealers, both backends
    alphas = {r["alpha"] for r in recs if r["alpha"] is not None}
    assert len(alphas) >= 2
    assert any(r["churn"] for r in recs)
    assert any(r["stragglers"] for r in recs)
    modes = {d["mode"] for r in recs for d in r["dealers"]}
    assert {"scale", "malformed"} <= modes
    assert {"sim", "wire"} <= {r["backend"] for r in recs}

    for rec in recs:
        assert rec["schema_version"] == 1
        if rec["aborted"]:
            assert rec["error"], "an aborted record must say why"
            continue
        assert rec["counters_match"] is True, rec["name"]
        assert set(rec["counters"]) == set(rec["counters_expected"])
        assert 0.0 <= rec["final_accuracy"] <= 1.0
        assert rec["accuracy_floor"] < rec["final_accuracy"]
        assert len(rec["outcomes"]) == rec["epochs"]


def test_bench_scenarios_dealer_blame_records():
    recs = _load_bench()["scenarios"]
    blamed = [r for r in recs if not r["aborted"] and r["banned"]]
    assert blamed, "a dealer-blame scenario must complete with a ban"
    for rec in blamed:
        victims = sorted(d["party"] for d in rec["dealers"])
        assert rec["banned"] == victims
        assert any(o["blamed_dealers"] for o in rec["outcomes"])
    ratios = [r["loss_ratio_vs_honest"] for r in recs
              if "loss_ratio_vs_honest" in r]
    assert ratios, "a poisoned scenario must pin its honest-twin ratio"
    assert all(r <= 1.2 for r in ratios)


# ---------------------------------------------------------------------------
# sim-vs-wire differentials (-m net): composed scenarios
# ---------------------------------------------------------------------------

wire = pytest.mark.net

_DIFF_FIELDS = ("outcomes", "banned", "final_loss", "final_accuracy",
                "counters", "counters_expected", "counters_match")


def _differential(name: str, **kw):
    sim_rec = run_scenario(ScenarioConfig(name=name + "_sim", **kw))
    wire_rec = run_scenario(ScenarioConfig(name=name + "_wire",
                                           backend="wire", **kw))
    for field in _DIFF_FIELDS:
        assert sim_rec[field] == wire_rec[field], \
            f"{name}: sim/wire diverge on {field}"
    assert sim_rec["counters_match"] is True
    return sim_rec


@wire
def test_wire_churn_straggler_scenario_bit_identical(net_log_dir):
    """Churn + stragglers composed, on real sockets: same memberships,
    same straggler verdicts, same counters, bit-identical final loss."""
    _differential(
        "churn_straggler", epochs=3, local_steps=1,
        samples_per_party=60, churn=ChurnConfig(seed=3),
        straggler=StragglerConfig(deadline_s=0.6, median_s=0.3,
                                  sigma=1.2, seed=7),
        wire_kwargs={"log_dir": net_log_dir})


@wire
def test_wire_poisoned_dealer_with_dropout_bit_identical(net_log_dir):
    """Poisoned committee-member dealer + straggling party composed:
    the wire's audit must blame, evict and re-elect exactly like the
    sim, and the cleaned means must agree bit-for-bit."""
    rec = _differential(
        "poison_dropout", epochs=3, local_steps=1,
        samples_per_party=60, norm_bound=8.0,
        dealers=(DealerConfig(party=1, mode="scale", round_index=1),),
        straggler=StragglerConfig(deadline_s=0.6, median_s=0.3,
                                  sigma=1.2, seed=7),
        wire_kwargs={"log_dir": net_log_dir})
    assert rec["banned"] == [1]
    assert rec["outcomes"][1]["blamed_dealers"] == [1]
    assert rec["outcomes"][1]["straggled"] == [3]
