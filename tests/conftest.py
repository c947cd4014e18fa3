"""Test bootstrap: make the suite collectable without ``hypothesis``.

The property-based tests use a small slice of the hypothesis API
(``given`` / ``settings`` / ``strategies.integers|lists|sampled_from``).
When the real package is available (see requirements-dev.txt) it is
used untouched; otherwise a minimal deterministic shim is installed in
``sys.modules`` *before* test modules import, so collection succeeds
and the properties still run over seeded random draws (boundary values
first, then uniform samples).  The shim does no shrinking — it exists
so `PYTHONPATH=src python -m pytest -q` runs green in minimal
environments, not to replace hypothesis in CI.  With the real package,
the default settings profile has no per-example deadline: the first
example of a property pays for JIT compilation, which a wall-clock
deadline would report as a flaky failure.

Every test's call phase runs under a time limit (``DEFAULT_TIME_LIMIT_S``,
or ``@pytest.mark.time_limit(seconds)``): a test past its limit fails
with its id, and a hang that never returns to Python still dumps every
thread's stack shortly after.
"""

from __future__ import annotations


import faulthandler
import os
import random
import re
import signal
import sys
import types

import pytest

from repro.compile_cache import use_compile_cache

# Persistent JAX compilation cache, shared by the test process and every
# spawned party worker (which inherit this environment): the Feldman
# fixed-base exponentiation JIT is a one-time cost per checkout instead
# of per process, which is what keeps the -m net VSS scenarios inside
# their round timeouts on a cold runner.
use_compile_cache()

#: seconds a test's call phase may run unless it carries a time_limit
DEFAULT_TIME_LIMIT_S = 120
_STDERR_FD = 2


def pytest_configure(config):
    global _STDERR_FD
    # the terminal's stderr, not the capture of the test that hangs:
    # a process that never returns would lose its captured output
    _STDERR_FD = os.dup(sys.__stderr__.fileno())
    config.addinivalue_line(
        "markers",
        "time_limit(seconds): fail the test if its call phase runs "
        f"longer than this (default {DEFAULT_TIME_LIMIT_S} s)")
    # deselected by default (pyproject addopts); CI's net-integration job
    # (-m "net or slow") and `-m slow` run them
    config.addinivalue_line(
        "markers",
        "slow: over a minute alone on a CPU runner; not in the default "
        "selection")
    # kernel differential tests carry the "kernels" marker so CI can run
    # them as a dedicated interpret-mode job (-m kernels); see
    # .github/workflows/ci.yml and DESIGN.md §7
    config.addinivalue_line(
        "markers",
        "kernels: Pallas-kernel differential tests (CPU interpret / TPU "
        "compiled); any skip must carry an asserted 'capability:' reason")
    # multi-process wire-transport integration tests: spawn party
    # worker subprocesses + TCP sockets; CI runs them as a dedicated
    # job with a hard 120s timeout and log upload (-m net)
    config.addinivalue_line(
        "markers",
        "net: multi-process TCP wire-transport integration tests "
        "(subprocesses + localhost sockets)")
    # malicious-security battery: tampering committee members must be
    # detected/blamed/evicted by the VSS layer (DESIGN.md §10); the
    # wire half also carries the net marker so the net CI job runs it
    config.addinivalue_line(
        "markers",
        "adversarial: VSS tampering battery (detection, blame, "
        "eviction, re-election)")
    # relay-tree hardening battery (ISSUE 10): commitment-bound
    # REGION_SUMs, audit-row escrow, fail-fast upload verdicts — the
    # net CI job runs these explicitly (-m "net and relay_tree") so a
    # marker-expression typo cannot silently deselect them
    config.addinivalue_line(
        "markers",
        "relay_tree: tree-relay hardening tests (region blame quorum, "
        "escrow audit, upload probes)")


class TimeLimitExceeded(BaseException):
    """A test ran past its time limit.  Not an ``Exception``, so neither
    the test nor hypothesis (which would shrink on it) catches it."""


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("time_limit")
    limit = marker.args[0] if marker else DEFAULT_TIME_LIMIT_S

    def expire(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} exceeded its time limit of {limit} s")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    # a hang inside C code never runs the handler above
    faulthandler.dump_traceback_later(limit + 30, file=_STDERR_FD)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def net_log_dir(tmp_path, request):
    """Per-test coordinator/party log directory.

    CI sets ``REPRO_NET_LOG_DIR`` so failing runs upload logs as
    artifacts; each test gets its own *subdirectory* of it (derived
    from the test's nodeid) so concurrently running tests — pytest-
    xdist workers — never append to each other's log files.  Ports are
    never shared state: every ``WireTransport`` binds port 0 and the
    OS-assigned ephemeral port is surfaced through the coordinator
    handshake to the spawned party workers, so ``-m net`` is xdist-safe
    end to end.  Locally logs land in pytest's tmp dir.
    """
    base = os.environ.get("REPRO_NET_LOG_DIR")
    if not base:
        return str(tmp_path)
    sub = os.path.join(base,
                       re.sub(r"[^A-Za-z0-9_.-]+", "_",
                              request.node.nodeid))
    os.makedirs(sub, exist_ok=True)
    return sub

try:
    import hypothesis  # real package wins
except ImportError:
    _DEFAULT_EXAMPLES = 20

    class _Strategy:
        def __init__(self, draw, boundaries=()):
            self._draw = draw
            self._boundaries = tuple(boundaries)

        def example(self, rnd, index):
            if index < len(self._boundaries):
                return self._boundaries[index]
            return self._draw(rnd)

    def _integers(min_value=0, max_value=2 ** 63):
        return _Strategy(lambda rnd: rnd.randint(min_value, max_value),
                         boundaries=(min_value, max_value))

    def _lists(elements, min_size=0, max_size=10):
        def draw(rnd):
            size = rnd.randint(min_size, max_size)
            return [elements._draw(rnd) for _ in range(size)]
        return _Strategy(draw)

    def _sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda rnd: rnd.choice(seq), boundaries=tuple(seq))

    def _booleans():
        return _Strategy(lambda rnd: rnd.choice([False, True]),
                         boundaries=(False, True))

    def _given(*strategies_args):
        def decorate(fn):
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_shim_max_examples",
                            _DEFAULT_EXAMPLES)
                rnd = random.Random(0xC0FFEE)
                for i in range(n):
                    drawn = tuple(s.example(rnd, i)
                                  for s in strategies_args)
                    fn(*args, *drawn, **kwargs)
            # NOT functools.wraps: copying __wrapped__ would make pytest
            # introspect the original signature and demand fixtures for
            # the property arguments
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            wrapper.__dict__.update(fn.__dict__)
            return wrapper
        return decorate

    def _settings(max_examples=_DEFAULT_EXAMPLES, **_ignored):
        def decorate(fn):
            fn._shim_max_examples = max_examples
            return fn
        return decorate

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.lists = _lists
    _st.sampled_from = _sampled_from
    _st.booleans = _booleans

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.__is_shim__ = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
else:
    hypothesis.settings.register_profile("repro", deadline=None)
    hypothesis.settings.load_profile("repro")
