"""The reduction from a profiler trace to the per-layer metrics, pinned.

``data/fedsgd.m3.xplane.pb.xz`` is a trace recorded on a TPU v5e of
three two_phase steps of qwen1.5-0.5b at batch 8 x 128, one party, with
the spans ``bench/kinds/train.py`` writes (``bench.step``,
``program.train_step``, ``bench.loss_to_host``).
``data/engine.n16.m3.ops.json.xz`` holds, of a trace recorded on a TPU
v5e of two rounds of the party engine (16 parties, 2^25 elements in
2^22 chunks), the device ops and the spans ``bench/kinds/engine.py``
writes.  The numbers below were read from them once; a change to the
reducer or to a reader that moves them changes what every later run
reports.  Run by hand: ``python -m pytest bench/tests``.
"""

import copy
import json
import lzma
import os

import pytest

from bench import harness
from bench.kinds import engine, train
from bench.trace import Trace

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DATA = os.path.join(HERE, "fedsgd.m3.xplane.pb.xz")
ENGINE_DATA = os.path.join(HERE, "engine.n16.m3.ops.json.xz")

#: the traced steps' shape: one party, batch 8 x 128
TRACED = {"mesh": [1, 1], "micro_batch": 8, "local_steps": 1, "seq": 128}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with lzma.open(DATA) as fh:
        path.write_bytes(fh.read())
    return Trace.from_file(str(path))


def test_reductions(trace):
    t0, t1 = trace.window("bench.step")
    assert trace.chips == 1
    assert (t1 - t0) / 1e9 == pytest.approx(0.46076039, rel=1e-9)
    assert trace.busy_s(t0, t1) == pytest.approx(0.45345805, rel=1e-9)
    assert trace.op_s("share_gen|reconstruct", t0, t1) == pytest.approx(
        0.248598239, rel=1e-9)
    top = trace.top_ops(t0, t1, 3)
    assert [n for n, _ in top] == ["_share_gen_jit", "fusion",
                                   "_reconstruct_jit"]
    assert top[0][1] == pytest.approx(0.186513364, rel=1e-9)
    gaps = trace.idle_gaps(t0, t1, 2)
    assert gaps[0][0] == "np.asarray(jax.Array)"
    assert gaps[0][1] == pytest.approx(0.007302324, rel=1e-6)
    # the idle time is all attributed
    idle = (t1 - t0) / 1e9 - trace.busy_s(t0, t1)
    assert sum(v for _, v in trace.idle_gaps(t0, t1, 100)) == \
        pytest.approx(idle, rel=1e-9)


def _reading(cell, work, trace):
    return harness.Reading(
        cell=cell, device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1},
        peaks=harness.peaks_for("TPU v5 lite"), work=work,
        setup_s=0.0, unit_s=[], window_s=0.0, trace=trace)


def _traced_cell():
    cell = copy.deepcopy(harness.resolve(harness.load_spec(),
                                         "qwen05b.fedsgd.m3"))
    cell.traffic.update(TRACED)
    return cell


def test_readers(trace):
    cell = _traced_cell()
    r = _reading(cell, train.work_counts(cell), trace)
    want = {"idle_share.train": 1.5848454334366768,
            "mfu.train": 9.486257380347162,
            "secure_agg_ms.train": 82.86607966666666,
            "secure_agg_roofline.train": 21.877402774615003}
    got = harness.read_metrics(harness.load_spec(), r, True)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        want, rel=1e-9)


def test_engine_readers():
    with lzma.open(ENGINE_DATA) as fh:
        data = json.load(fh)
    tr = Trace({int(k): [tuple(e) for e in v]
                for k, v in data["devices"].items()},
               [tuple(e) for e in data["host"]])
    cell = harness.resolve(harness.load_spec(), "engine.n16.m3")
    r = _reading(cell, engine.work_counts(cell), tr)
    got = harness.read_metrics(harness.load_spec(), r, True)
    # 92 * 2^25 bytes a round at 819 GB/s against 158.05 ms busy a round
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        {"idle_share.engine": 95.24575142513949,
         "share_sum_roofline.engine": 2.3848757747077007}, rel=1e-9)


def test_a_metric_that_reads_nothing_fails_the_run():
    cell = _traced_cell()
    r = _reading(cell, train.work_counts(cell), None)
    with pytest.raises(harness.BenchError, match="found nothing to read"):
        harness.read_metrics(harness.load_spec(), r, True)


def test_work_counts():
    cell = _traced_cell()
    model = cell.config["model"]
    assert harness.load_work("dense_lm_params").count(model) == 463987712
    w = train.work_counts(cell)
    assert w["tokens_per_step"] == 1024
    assert w["flops_per_token"] == 6 * 463987712 + 6 * 24 * 1024 * 129
    assert w["secure_agg_bytes_per_party_step"] == 32 * 463987712
    e = engine.work_counts(harness.resolve(harness.load_spec(),
                                           "engine.n16.m3"))
    assert e["share_sum_bytes_per_round"] == 92 * 2 ** 25
