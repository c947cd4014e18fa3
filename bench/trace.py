"""Profiler trace of a window, and its reduction to device time.

``record(dir)`` traces with the Python tracer off, so the Python
thread's host line holds the benchmark's own spans (``bench.*``,
``program.*``) and JAX's dispatch events.  ``Trace.load(dir)`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``: the ops of each ``/device:TPU:<i>`` plane
(its ``XLA Ops`` line, named by the HLO instruction, e.g.
``_share_gen_jit``, ``fusion``, ``all-reduce``) and that host line, on
one clock.  Op names drop their ``.<n>`` suffix.

Every reduction takes a window ``(t0, t1)`` in nanoseconds and averages
over the chips: device busy time is the union of op intervals, so an op
nested in another (a loop body inside its ``while``) counts once.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

_SUFFIX = re.compile(r"\.\d+$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the host line holding the benchmark's spans is the Python thread's,
#: named after the process (``python``, ``python3``, ...)
SPAN_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


@contextlib.contextmanager
def record(log_dir: str):
    """Trace the enclosed code into ``log_dir``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    """Device ops per chip and host spans of one traced window."""

    def __init__(self, devices: dict, host: list):
        #: chip index -> [(op name, start ns, end ns)], sorted by start
        self.devices = {k: sorted(v, key=lambda x: x[1])
                        for k, v in devices.items()}
        #: [(name, start ns, end ns)] of the host's Python line
        self.host = sorted(host, key=lambda x: (x[1], -x[2]))

    @classmethod
    def load(cls, log_dir: str) -> "Trace":
        files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"{len(files)} traces under {log_dir}")
        return cls.from_file(files[0])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices, host = {}, []
        for plane in data.planes:
            dev = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if dev and line.name == OPS_LINE:
                    devices[int(dev.group(1))] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                elif plane.name == HOST_PLANE:
                    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
                    if any(n.startswith(SPAN_PREFIX) for n, _, _ in events):
                        host = events
        return cls(devices, host)

    @property
    def chips(self) -> int:
        return len(self.devices)

    def spans(self, name: str) -> list:
        return [(s, e) for n, s, e in self.host if n == name]

    def window(self, unit: str) -> tuple[float, float]:
        """From the first ``unit`` span's start to the last one's end."""
        spans = self.spans(unit)
        if not spans:
            names = sorted({n for n, _, _ in self.host})[:40]
            raise RuntimeError(f"no {unit!r} span among the trace's "
                               f"{len(self.host)} host events: {names}")
        return spans[0][0], spans[-1][1]

    def _clipped(self, chip, t0, t1, pattern=None):
        rx = re.compile(pattern) if pattern else None
        for name, s, e in self.devices[chip]:
            if e <= t0 or s >= t1 or (rx and not rx.search(name)):
                continue
            yield name, max(s, t0), min(e, t1)

    def busy_s(self, t0, t1) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for chip in self.devices:
            tot += sum(e - s for s, e in _union(
                (s, e) for _, s, e in self._clipped(chip, t0, t1)))
        return tot / self.chips / 1e9

    def op_s(self, pattern: str, t0, t1) -> float:
        """Seconds of ops whose name matches ``pattern``, averaged over
        the chips (the union of their intervals on each chip)."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for chip in self.devices:
            tot += sum(e - s for s, e in _union(
                (s, e) for _, s, e in self._clipped(chip, t0, t1, pattern)))
        return tot / self.chips / 1e9

    def top_ops(self, t0, t1, k: int = 10) -> list:
        """``[[op name, seconds]]`` of the ops that took most device time
        (each op's own interval, averaged over the chips)."""
        tot: dict[str, float] = {}
        for chip in self.devices:
            for name, s, e in self._clipped(chip, t0, t1):
                tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        rows = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, v / max(self.chips, 1)] for n, v in rows]

    def idle_gaps(self, t0, t1, k: int = 10) -> list:
        """``[[what the host was doing, seconds]]``: the device's idle
        time on chip 0, each gap put to the innermost host span around
        its middle, summed by span name; the ``k`` largest."""
        if not self.devices:
            return []
        chip = min(self.devices)
        busy = _union((s, e) for _, s, e in self._clipped(chip, t0, t1))
        gaps, prev = [], t0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if t1 > prev:
            gaps.append((prev, t1))
        # one sweep: the host line's spans are well nested
        tot: dict[str, float] = {}
        stack, i = [], 0
        for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = (s + e) / 2
            while i < len(self.host) and self.host[i][1] <= mid:
                name, hs, he = self.host[i]
                while stack and stack[-1][1] < hs:
                    stack.pop()
                stack.append((name, he))
                i += 1
            while stack and stack[-1][1] < mid:
                stack.pop()
            name = stack[-1][0] if stack else "(no host span)"
            tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]
