"""``repro.api.ExperimentSpec``: the unified typed experiment surface.

Pins (a) the JSON round-trip + loud unknown-key rejection, (b) the
deprecation shims — old ``agg_kwargs`` call sites warn but stay
bit-identical to the typed path — and (c) that every driver accepts a
spec directly (``run_fedavg``, ``FLSimulation``, ``make_transport``,
``run_scenario``)."""

import dataclasses
import json

import numpy as np
import pytest

from repro.api import ExperimentSpec, make_transport
from repro.deprecation import ReproDeprecationWarning
from repro.fl.rounds import FedAvgConfig, run_fedavg
from repro.fl.scenarios import ChurnConfig, ScenarioConfig, run_scenario
from repro.fl.simulation import FLSimulation
from repro.fl.transport import TwoPhaseTransport


# ---------------------------------------------------------------------------
# Spec construction + JSON round-trip
# ---------------------------------------------------------------------------

def test_spec_is_frozen_and_validates():
    spec = ExperimentSpec(n=8, cohort=4)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.n = 9
    with pytest.raises(ValueError, match="pipeline"):
        ExperimentSpec(n=8, pipeline=True)          # needs cohort mode
    with pytest.raises(ValueError, match="cohort"):
        ExperimentSpec(n=8, cohort=9)
    with pytest.raises(ValueError, match="backend"):
        ExperimentSpec(n=8, backend="carrier-pigeon")
    with pytest.raises(ValueError, match="pair"):
        ExperimentSpec(n=8, frac_bits=12)           # clip missing


def test_spec_json_round_trip_through_text():
    spec = ExperimentSpec(
        n=16, m=3, scheme="shamir", shamir_degree=1, vss=True,
        norm_bound=5.0, cohort=6, pipeline=True, backend="wire",
        frac_bits=14, clip=32.0, compress_topk=None,
        scenario=ScenarioConfig(name="t", churn=ChurnConfig()))
    back = ExperimentSpec.from_json(json.loads(json.dumps(spec.to_json())))
    assert back == spec
    assert isinstance(back.scenario, ScenarioConfig)
    assert isinstance(back.scenario.churn, ChurnConfig)


def test_spec_unknown_keys_rejected_with_hint():
    with pytest.raises(ValueError, match="did you mean 'cohort'"):
        ExperimentSpec.from_json({"n": 4, "cohrot": 2})
    with pytest.raises(ValueError, match="scenario"):
        ExperimentSpec.from_json(
            {"n": 4, "scenario": {"name": "x", "epochz": 3}})


# ---------------------------------------------------------------------------
# Conversions: the spec composes the per-layer configs
# ---------------------------------------------------------------------------

def test_spec_converts_to_fedavg_and_wire_configs():
    spec = ExperimentSpec(n=10, m=3, scheme="shamir", shamir_degree=1,
                          vss=True, cohort=5, pipeline=True,
                          backend="wire", lease_s=12.0)
    fa = spec.fedavg_config()
    assert (fa.n_parties, fa.committee, fa.cohort) == (10, 3, 5)
    assert fa.backend == "wire" and fa.vss
    assert fa.wire_kwargs["pipeline"] and fa.wire_kwargs["lease_s"] == 12.0
    wc = spec.wire_config()
    assert (wc.n, wc.cohort, wc.pipeline, wc.lease_s) == (10, 5, True,
                                                          12.0)
    assert wc.vss and wc.scheme == "shamir"


def test_make_transport_builds_typed_sim_transport():
    spec = ExperimentSpec(n=6, m=3, cohort=4, seed=9)
    tr = make_transport(spec)
    assert isinstance(tr, TwoPhaseTransport)
    assert tr.cohort == 4 and tr.seed == 9
    with pytest.raises(TypeError, match="ExperimentSpec"):
        make_transport({"backend": "sim"})


def test_flsimulation_accepts_spec_directly():
    spec = ExperimentSpec(n=6, m=3, seed=5, cohort=4)
    sim = FLSimulation(spec)
    assert (sim.n, sim.m, sim.seed) == (6, 3, 5)
    assert sim.transports["two_phase"].cohort == 4
    sim.elect_committee()
    assert set(sim.committee) <= set(
        sim.transports["two_phase"].cohort_ids)


def test_run_scenario_accepts_spec():
    # spec plumbing only: VSS is the subject of test_vss_adversarial.py,
    # test_relay_hardening_props.py and test_scenarios.py
    spec = ExperimentSpec(
        n=4, m=3, epochs=2, local_steps=1, scheme="shamir",
        shamir_degree=1, vss=False, seed=1,
        scenario=ScenarioConfig(name="spec-smoke", batch_size=16,
                                samples_per_party=40))
    rec = run_scenario(spec)
    assert rec["name"] == "spec-smoke"
    assert not rec["aborted"] and rec["error"] is None
    # the spec's shared fields won over the scenario's defaults
    assert (rec["n"], rec["m"], rec["epochs"], rec["seed"]) == (4, 3, 2, 1)


# ---------------------------------------------------------------------------
# Deprecation shims: old kwargs paths warn but stay bit-identical
# ---------------------------------------------------------------------------

def _tiny_fedavg(cfg):
    d = 5

    def step(params, batch):
        return {"w": params["w"] - 0.1 * batch}

    def batches(i, epoch, it):
        return np.full(d, 0.02 * (i + 1), dtype=np.float32)

    return run_fedavg(cfg, {"w": np.zeros(d, dtype=np.float32)},
                      step, batches)


def test_agg_kwargs_shim_warns_and_matches_typed_path_bitwise():
    new = _tiny_fedavg(FedAvgConfig(n_parties=5, epochs=2, local_steps=1,
                                    seed=7, backend="sim", vss=False))
    with pytest.warns(ReproDeprecationWarning, match="agg_kwargs"):
        old = _tiny_fedavg(FedAvgConfig(n_parties=5, epochs=2,
                                        local_steps=1, seed=7,
                                        agg_kwargs={"backend": "sim",
                                                    "vss": False}))
    np.testing.assert_array_equal(np.asarray(old.params["w"]),
                                  np.asarray(new.params["w"]))
    assert (old.msg_num, old.msg_size) == (new.msg_num, new.msg_size)


def test_spec_path_matches_old_config_path_bitwise():
    spec = ExperimentSpec(n=5, epochs=2, local_steps=1, seed=7)
    via_spec = _tiny_fedavg(spec)
    via_cfg = _tiny_fedavg(FedAvgConfig(n_parties=5, epochs=2,
                                        local_steps=1, seed=7))
    np.testing.assert_array_equal(np.asarray(via_spec.params["w"]),
                                  np.asarray(via_cfg.params["w"]))
    assert via_spec.msg_num == via_cfg.msg_num


def test_agg_kwargs_unknown_key_still_fails_with_hint():
    cfg = FedAvgConfig(n_parties=4, epochs=1,
                       agg_kwargs={"chunk_elms": 8})
    with pytest.warns(ReproDeprecationWarning):
        with pytest.raises(TypeError, match="did you mean"):
            _tiny_fedavg(cfg)
