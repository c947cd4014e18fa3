"""What every cell shares: finding its files by name, the chip check, the
record of kernel dispatch decisions, the comparison that decides
``correct``, and the result line.

Nothing here knows a cell.  A cell is ``BENCHMARK.json``'s workload
entry; its configuration file names its kind (``bench/kinds/<kind>.py``),
which builds the system under test, runs the window and reads the
numbers that are compared.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
    """The run cannot produce a result: no chip, a file missing, a kernel
    that did not compile."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


_MODULES: dict[str, object] = {}


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise BenchError(f"missing benchmark file {path}")
        name = "bench_file_" + os.path.relpath(path, BENCH).replace(
            os.sep, "__").replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    config_path: str
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/cells/<cell>.json "limits"

    @property
    def reference_path(self) -> str:
        return self.config_path[:-len(".json")] + ".reference.py"

    @property
    def kind(self) -> str:
        return self.config["kind"]


def load_spec() -> dict:
    if not os.path.isfile(SPEC_FILE):
        raise BenchError(f"no {SPEC_FILE}")
    return load_json(SPEC_FILE)


def resolve(spec: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "cells", workload + ".json"))
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json(cfg_path), config_path=cfg_path,
                traffic=traffic, limits=limits["limits"])


def metrics_for(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports, in ``BENCHMARK.json`` order.

    ``--trace 0``: the end-to-end metrics whose ``workloads`` name the
    cell (or that have no such key).  ``--trace 1``: the per-layer
    metrics that name the cell, or, without a ``workloads`` key, those
    that move an end-to-end metric this cell reports.
    """
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def read_metrics(spec: dict, reading, trace: bool) -> dict:
    """Every metric the run reports, by its reader.

    A reader returns None where it finds nothing to read; a metric that
    ``BENCHMARK.json`` names for this cell and that reads nothing (a
    kernel skipped or renamed, a trace without the cell's spans) fails
    the run rather than leaving the metric out."""
    out = {}
    for m in metrics_for(spec, reading.cell.name, trace):
        value = metric_reader(m["name"]).read(reading)
        if value is None:
            raise BenchError(f"metric {m['name']} found nothing to read "
                             f"in {reading.cell.name}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def load_work(name: str):
    """A count of work from shapes: ``bench/work/<name>.py``."""
    return load_module(os.path.join(BENCH, "work", name + ".py"))


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         "bench/peaks.json")
    return table["devices"][device_kind]


class DecisionLog:
    """Records every kernel dispatch decision made through ``decide``,
    with the program function that asked (``<module>.<function>``).

    ``enforce=False`` (CPU tests, where kernels run as their oracles)
    records without requiring ``compiled``; the calls that
    ``require_compiled`` expects are made on every platform."""

    def __init__(self, dispatch, enforce: bool = True):
        self.decisions = []
        self.enforce = enforce
        real = dispatch.decide

        def recording(*args, **kwargs):
            dec = real(*args, **kwargs)
            frame = sys._getframe(1)
            caller = (f"{frame.f_globals.get('__name__', '')}."
                      f"{frame.f_code.co_qualname}")
            self.decisions.append((caller, dec))
            return dec

        dispatch.decide = recording

    def require_compiled(self, phase: str, expect: dict | None = None):
        """Every decision since the last call resolved to ``compiled``,
        and each program function in ``expect`` decided at least that
        many times (a path that skips sharing or reconstruction is a
        different result, not a faster one)."""
        decs, self.decisions = self.decisions, []
        bad = sorted({d.mode for _, d in decs if d.mode != "compiled"})
        if bad and self.enforce:
            raise BenchError(f"{phase}: kernel calls resolved to {bad}, "
                             "not compiled")
        for caller, least in (expect or {}).items():
            got = sum(c == caller for c, _ in decs)
            if got < least:
                raise BenchError(f"{phase}: {caller} was called {got} "
                                 f"times, the cell needs {least}")
        return len(decs)


class CompileCount:
    """Executables that JAX compiles, or loads from its persistent cache,
    while ``on``: the window should have none, every shape having been
    warmed in set-up."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count, self.seconds = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.count += 1
            self.seconds += duration


def process_elapsed_s() -> float:
    """Seconds since this process started, from ``/proc`` (0 if absent)."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


@dataclasses.dataclass
class Reading:
    """What a metric reader sees of one run."""

    cell: Cell
    device: dict                 # platform, kind, count
    peaks: dict                  # bench/peaks.json row of the device kind
    work: dict                   # counts from shapes (bench/work)
    setup_s: float
    unit_s: list                 # host-clock duration of each step/round
    window_s: float              # host-clock length of the window
    trace: object = None         # bench.trace.Trace of a --trace 1 run


def compare(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number against its limit; ``correct`` iff all hold.

    Every number is a gap from the reference (lower is closer); a
    number that is not finite fails.
    """
    if set(numbers) != set(limits):
        raise BenchError(f"compared numbers {sorted(numbers)} do not "
                         f"match the cell's limits {sorted(limits)}")
    out, ok = {}, True
    for name in limits:
        value, limit = float(numbers[name]), float(limits[name])
        held = math.isfinite(value) and value <= limit
        ok = ok and held
        out[name] = {"value": value, "limit": limit}
    return ok, out


def print_timings(phases: dict, unit_s: list, setup_s: float,
                  compiles: CompileCount) -> None:
    """Where the set-up went, the window's slowest units and what
    compiled in the window, on stderr."""
    parts = " ".join(f"{k}={v:.3f}" for k, v in phases.items())
    print(f"set-up {setup_s:.3f} s: {parts}", file=sys.stderr, flush=True)
    if unit_s:
        slow = sorted(enumerate(unit_s), key=lambda x: -x[1])[:5]
        med = sorted(unit_s)[len(unit_s) // 2]
        print(f"window: {len(unit_s)} units, median {med:.6f} s, slowest "
              + ", ".join(f"#{i} {d:.6f} s" for i, d in slow),
              file=sys.stderr, flush=True)
    print(f"window: {compiles.count} executables compiled or loaded from "
          f"the cache ({compiles.seconds:.3f} s)", file=sys.stderr,
          flush=True)


def print_result(result: dict) -> None:
    """Compared numbers on standard error, then the result line."""
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
