"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s`` from the process's start): find the chip,
build the cell's system under test from the seed and warm every shape
the window uses.  Then the window: ``--seconds`` of steps or rounds with
the profiler off (``--trace 0``, end-to-end metrics), or a short traced
window of the traffic's ``trace_units`` (``--trace 1``, per-layer
metrics).  Then the peak device memory is read, the program's state is
freed, and the plain reference decides ``correct``.  The last line of
standard output is the result as JSON; the compared numbers, each with
its limit, are the last lines of standard error and the result's last
key.  A run that finds no TPU, fewer chips than the cell asks for, or a
kernel call that does not resolve to ``compiled`` exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import os
import shutil
import sys
import tempfile
import time

_T0 = time.perf_counter()

from bench import harness  # noqa: E402

# what this process had run before ``_T0``
_STARTED = harness.process_elapsed_s()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _use_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout."""
    os.makedirs(harness.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            require_chip: bool = True, cell: harness.Cell | None = None):
    """One run; returns the result dict.  ``cell`` replaces the cell's
    files (tests run small sizes), and ``require_chip=False`` skips the
    look for a TPU and for compiled kernels (tests run on the CPU)."""
    spec = harness.load_spec()
    cell = cell or harness.resolve(spec, workload)
    if not os.path.isdir(os.path.join(harness.SRC, "repro")):
        raise harness.BenchError(f"no program under {harness.SRC}")
    if harness.SRC not in sys.path:
        sys.path.insert(0, harness.SRC)
    _use_cache()

    import jax

    from repro.kernels import dispatch

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or dispatch.probe() != dispatch.CAP_TPU):
        raise harness.BenchError(
            f"JAX finds no TPU (platform {devices[0].platform})")
    if len(devices) < cell.chips:
        raise harness.BenchError(f"{len(devices)} devices, the cell asks "
                                 f"for {cell.chips}")
    if require_chip and os.environ.get(dispatch.ENV_VAR, "").strip().lower() \
            not in ("", "auto", dispatch.MODE_COMPILED):
        raise harness.BenchError(f"{dispatch.ENV_VAR} forces a kernel mode "
                                 "other than compiled")
    devices = devices[:cell.chips]
    kind = devices[0].device_kind
    peaks = harness.peaks_for(kind) if require_chip else {}
    log = harness.DecisionLog(dispatch, enforce=require_chip)
    compiles = harness.CompileCount()
    system = importlib.import_module(f"bench.kinds.{cell.kind}")

    units = int(cell.traffic["trace_units"]) if trace else None
    t_session = time.perf_counter()
    session = system.Session(cell, seed, devices, log, seconds,
                             trace_units=units)
    # the set-up's objects leave the collector's generations, so that no
    # collection in the window walks them
    gc.collect()
    gc.freeze()
    t_window = time.perf_counter()
    compiles.on = True
    tr = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            from bench import trace as trace_mod
            with trace_mod.record(log_dir):
                unit_s, window_s, failed = session.window(seconds, units)
            tr = trace_mod.Trace.load(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        unit_s, window_s, failed = session.window(seconds)
    compiles.on = False
    log.require_compiled("window")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    session.release()
    t_check = time.perf_counter()
    correct, compared = harness.compare(session.compared(devices),
                                        cell.limits)
    phases = {"start": _STARTED + (t_session - _T0), **session.phases,
              "reference (not set-up)": time.perf_counter() - t_check}
    harness.print_timings(phases, unit_s, _STARTED + (t_window - _T0),
                          compiles)

    reading = harness.Reading(
        cell=cell, device={"platform": devices[0].platform, "kind": kind,
                           "count": len(devices)},
        peaks=peaks, work=session.work,
        setup_s=_STARTED + (t_window - _T0), unit_s=unit_s,
        window_s=window_s, trace=tr)
    metrics = harness.read_metrics(spec, reading, trace)
    device = dict(reading.device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(unit_s),
              "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        unit = "bench." + session.work["unit"]
        t0, t1 = tr.window(unit)
        device.update(busy_s=tr.busy_s(t0, t1), window_s=(t1 - t0) / 1e9)
        result["breakdown"] = {"device_ops": tr.top_ops(t0, t1),
                               "idle_gaps": tr.idle_gaps(t0, t1)}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
