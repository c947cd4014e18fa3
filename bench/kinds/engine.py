"""A party-engine cell: rounds of the batched secure aggregation.

Set-up builds the transport as ``repro.api.make_transport(ExperimentSpec
(...))`` makes it, elects the committee, makes the pool of party updates
on the device from the seed (``pool_blocks`` distinct ``[n, chunk]``
blocks of values on a 2^-22 grid in [-1, 1)) and runs one whole round,
which compiles every shape a round uses.  The window is a closed loop of
back-to-back rounds ``transport.aggregate(PartySource(...),
round_index=r)`` with ``r`` increasing, each ending when its mean is
ready on the device.  Chunk ``c`` of round ``r`` reads pool block
``(c * chunk_stride + r * round_stride) % pool_blocks``: no update is
generated or uploaded in the window.  Two rounds of the window, drawn
from the seed by reservoir sampling, keep their means for the
comparison.
"""

from __future__ import annotations

import contextlib
import random
import time

from bench import harness
from bench.kinds.train import key_of, seed_words

#: Grid of the updates: integers in [-2^22, 2^22) times 2^-22.
GRID_BITS = 22


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to the engine (its mask streams; the
    engine's key derivation runs eagerly, so it compiles nothing)."""
    return int(seed_words(seed, 8)[7] & 0x7FFFFFFF)


def make_pool(key, blocks: int, parties: int, elems: int):
    import jax
    import jax.numpy as jnp

    k = jax.random.randint(key, (blocks, parties, elems), -(1 << GRID_BITS),
                           1 << GRID_BITS)
    x = k.astype(jnp.float32) * jnp.float32(2.0 ** -GRID_BITS)
    return tuple(x[i] for i in range(blocks))


def work_counts(cell) -> dict:
    cfg = cell.config
    return {"share_sum_bytes_per_round": harness.load_work(
        "share_sum_bytes").bytes_per_round(
            cfg["parties"], cfg["committee"], cfg["update_elems"]),
        "unit": "round"}


class Rounds:
    """Which pool block each chunk of each round reads."""

    def __init__(self, cell):
        cfg, tr = cell.config, cell.traffic
        self.n, self.d = cfg["parties"], cfg["update_elems"]
        self.chunk = cfg["chunk_elems"]
        self.chunks = -(-self.d // self.chunk)
        self.blocks = tr["pool_blocks"]
        self.c_stride, self.r_stride = tr["chunk_stride"], tr["round_stride"]

    def block(self, r: int, c: int) -> int:
        return (c * self.c_stride + r * self.r_stride) % self.blocks


def pool_on(cell, seed: int, device):
    """The seed's pool blocks on ``device``."""
    import jax

    rounds = Rounds(cell)
    return jax.jit(lambda k: make_pool(k, rounds.blocks, rounds.n,
                                       rounds.chunk),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))(
        key_of(seed, 2))


class Session:
    """The transport with its committee and update pool."""

    def __init__(self, cell, seed: int, devices, log, seconds: float,
                 trace_units: int | None = None):
        import jax

        from repro.api import ExperimentSpec, make_transport

        if len(devices) != 1:
            raise harness.BenchError("the party engine runs on one chip")
        self.cell, self.seed = cell, seed
        self.phases, t = {}, time.perf_counter()
        cfg = cell.config
        self.rounds = Rounds(cell)
        self.work = work_counts(cell)
        self._ctx = contextlib.ExitStack()
        self._ctx.enter_context(jax.default_device(devices[0]))
        spec = ExperimentSpec(
            n=cfg["parties"], m=cfg["committee"], scheme=cfg["scheme"],
            seed=program_seed(seed), chunk_elems=cfg["chunk_elems"],
            vote_batch=cfg["vote_batch"],
            frac_bits=cfg["codec"]["frac_bits"], clip=cfg["codec"]["clip"])
        self.tr = make_transport(spec)
        self.tr.elect(0)
        self.phases["transport"], t = (time.perf_counter() - t,
                                       time.perf_counter())
        self.pool = pool_on(cell, seed, devices[0])
        tail = self.rounds.d % self.rounds.chunk
        self.tails = tuple(b[:, :tail] for b in self.pool) if tail else ()
        self.kept = {}
        self.round = 0
        jax.block_until_ready(self.pool)
        self.phases["pool"], t = time.perf_counter() - t, time.perf_counter()
        self._aggregate(0).block_until_ready()
        self.phases["warm_round"] = time.perf_counter() - t
        self.round = 1
        log.require_compiled("set-up", {
            k: self.rounds.chunks
            for k in cell.traffic["calls_per_chunk"]})

    def _source(self, r: int):
        from repro.core.aggregation import PartySource

        rounds = self.rounds

        def block(p_lo, p_hi, e_lo, e_hi):
            c = e_lo // rounds.chunk
            full = e_hi - e_lo == rounds.chunk
            blk = (self.pool if full else self.tails)[rounds.block(r, c)]
            return blk if (p_lo, p_hi) == (0, rounds.n) else blk[p_lo:p_hi]

        return PartySource(block, (rounds.n, rounds.d))

    def _aggregate(self, r: int):
        return self.tr.aggregate(self._source(r), round_index=r)

    def window(self, seconds: float, units: int | None = None):
        """Back-to-back rounds for ``seconds`` (or exactly ``units``)."""
        import jax

        keep = self.cell.traffic["checked_rounds"]
        pick = random.Random(self.seed)
        durs = []
        t0 = end = time.perf_counter()
        while (len(durs) < units) if units is not None else \
                (time.perf_counter() - t0 < seconds):
            ts = time.perf_counter()
            r = self.round
            with jax.profiler.StepTraceAnnotation("bench.round", step_num=r):
                with jax.profiler.TraceAnnotation("program.aggregate"):
                    out = self._aggregate(r)
                with jax.profiler.TraceAnnotation("bench.block_until_ready"):
                    out.block_until_ready()
            end = time.perf_counter()
            durs.append(end - ts)
            # reservoir sample of the window's rounds, drawn from the seed
            if len(self.kept) < keep:
                self.kept[r] = out
            else:
                j = pick.randrange(len(durs))
                if j < keep:
                    del self.kept[sorted(self.kept)[j]]
                    self.kept[r] = out
            del out
            self.round += 1
        return durs, end - t0, 0

    def release(self):
        """Free the pool; the kept means stay for the comparison."""
        del self.pool, self.tails

    def compared(self, devices) -> dict:
        """Kept means vs the exact mean; wire counters vs Eqs. 3-6."""
        ref = harness.load_module(self.cell.reference_path)
        pool = pool_on(self.cell, self.seed, devices[0])
        gap = max(ref.mean_gap(out, [pool[self.rounds.block(r, c)]
                                     for c in range(self.rounds.chunks)],
                               self.rounds.chunk, self.rounds.d)
                  for r, out in self.kept.items())
        cfg = self.cell.config
        net = self.tr.net
        counted = {ph: (net.stats(ph).msg_num, net.stats(ph).msg_size)
                   for ph in ("phase1", "phase2_upload", "phase2_exchange",
                              "phase2_broadcast")}
        wrong = ref.counter_mismatches(
            counted, n=cfg["parties"], m=cfg["committee"],
            s=cfg["update_elems"], b=cfg["vote_batch"], rounds=self.round)
        self._ctx.close()
        return {"mean": gap, "counters": float(wrong)}


def upper_readings(cell, seed: int, devices, faults) -> dict:
    """Control and planted faults, each in the program's place."""
    import jax

    ref = harness.load_module(cell.reference_path)
    rounds = Rounds(cell)
    pool = pool_on(cell, seed, devices[0])
    r = 1

    def blocks(rr):
        return [pool[rounds.block(rr, c)] for c in range(rounds.chunks)]

    out = {}
    for name in ("control",) + tuple(faults):
        with jax.default_device(devices[0]):
            fake = ref.stand_in(blocks(r), blocks(r - 1), name)
            out[name] = {"mean": ref.mean_gap(fake, blocks(r), rounds.chunk,
                                              rounds.d),
                         "counters": 0.0}
    return out
