"""A run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run but for the look for a chip
(``bench.run.execute(require_chip=False)``) on a small cell on the CPU,
with one fault planted in the program beneath the window: a step that
returns its state unchanged; half of the batch left out, the mean taken
over the rest; the exchange between chips left out; an answer altered
where it is produced; a round that returns an earlier round's answer.
The unbroken runs read ``correct`` true.  Run by hand:
``python -m pytest bench/tests``.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from bench import harness, run
from bench.tests.small import small_cell

SEED = 2 ** 31 + 99
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


#: FedAvg: local SGD steps on microbatches before each secure
#: aggregation (no cell of BENCHMARK.json yet), through the same code
LOCAL_STEPS = {"local_steps": 4, "micro_batch": 2}


def _correct(workload: str, **traffic) -> bool:
    res = run.execute(workload, SEED, 1.0, False, require_chip=False,
                      cell=small_cell(workload, **traffic))
    assert res["attempted"] > 0
    return res["correct"]


@pytest.mark.parametrize("workload,traffic", [
    ("qwen05b.fedsgd.m3", {}), ("engine.n16.m3", {}),
    ("qwen05b.fedsgd.m3", LOCAL_STEPS)], ids=["fedsgd", "engine", "fedavg"])
def test_sound_run_is_correct(workload, traffic):
    assert _correct(workload, **traffic)


def _unchanged(monkeypatch):
    from repro.launch import steps
    monkeypatch.setattr(steps, "adamw_update",
                        lambda grads, state, params, step, cfg: (params, state))


def _half_batch(monkeypatch):
    from repro.launch import steps
    real = steps.get_api

    def half_api(cfg):
        api = real(cfg)

        def loss(params, batch, cfg, **kw):
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            return api.loss_fn(params, half, cfg, **kw)

        return dataclasses.replace(api, loss_fn=loss)

    monkeypatch.setattr(steps, "get_api", half_api)


def _altered(monkeypatch):
    from repro.fl import spmd
    real = spmd.reconstruct

    def bad(*args, **kwargs):
        out = real(*args, **kwargs)
        return out.reshape(-1).at[0].add(16.0).reshape(out.shape)

    monkeypatch.setattr(spmd, "reconstruct", bad)


@pytest.mark.parametrize("traffic", [{}, LOCAL_STEPS],
                         ids=["fedsgd", "fedavg"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
def test_train_fault_is_caught(monkeypatch, traffic, fault):
    fault(monkeypatch)
    assert not _correct("qwen05b.fedsgd.m3", **traffic)


def test_train_step_without_sharing_fails_the_run(monkeypatch):
    """At one party the mean is the party's own gradient, so a step that
    skips share_gen and reconstruct would still compare well; it is a
    different result, and the run fails in set-up."""
    from repro.launch import steps
    monkeypatch.setattr(steps, "secure_aggregate_tree",
                        lambda tree, **kw: tree)
    with pytest.raises(harness.BenchError, match="share_gen"):
        run.execute("qwen05b.fedsgd.m3", SEED, 0.3, False,
                    require_chip=False,
                    cell=small_cell("qwen05b.fedsgd.m3"))


def _engine_altered(monkeypatch):
    from repro.core.aggregation import SecureAggregator
    real = SecureAggregator.reconstruct_mean

    def bad(self, *args, **kwargs):
        return real(self, *args, **kwargs).at[0].add(1.0)

    monkeypatch.setattr(SecureAggregator, "reconstruct_mean", bad)


def _engine_half_batch(monkeypatch):
    from repro.core.aggregation import SecureAggregator
    real = SecureAggregator.aggregate_stream

    def half(self, flats, *, party_ids, **kwargs):
        kwargs.pop("n", None)
        return real(self, flats, party_ids=party_ids[:len(party_ids) // 2],
                    **kwargs)

    monkeypatch.setattr(SecureAggregator, "aggregate_stream", half)


def _engine_stale(monkeypatch):
    from repro.fl.transport import TwoPhaseTransport
    real = TwoPhaseTransport.aggregate
    first = {}

    def stale(self, *args, **kwargs):
        return first.setdefault("out", real(self, *args, **kwargs))

    monkeypatch.setattr(TwoPhaseTransport, "aggregate", stale)


@pytest.mark.parametrize("fault", [_engine_altered, _engine_half_batch,
                                   _engine_stale])
def test_engine_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    assert not _correct("engine.n16.m3")


_MESH_RUN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import run
from bench.tests.small import small_cell
if {drop}:
    from repro.fl import spmd

    class _Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def psum(x, axis_name):
            return x          # the shares never cross the party axis

    class _Jax:
        lax = _Lax()

        def __getattr__(self, name):
            return getattr(jax, name)

    spmd.jax = _Jax()
res = run.execute("qwen05b.fedsgd.m3", {seed}, 3.0, False,
                  require_chip=False,
                  cell=small_cell("qwen05b.fedsgd.m3", chips=4, mesh=[4, 1]))
print(json.dumps(res["correct"]))
"""


@pytest.mark.parametrize("drop", [False, True], ids=["sound", "no_exchange"])
def test_four_party_exchange_left_out_is_caught(drop):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _MESH_RUN.format(root=ROOT, src=os.path.join(ROOT, "src"),
                            drop=drop, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) is (not drop)
