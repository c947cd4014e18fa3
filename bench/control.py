"""Upper readings of the comparison that decides ``correct``.

    python3 -m bench.control --workload <cell> --seeds 11,12,13

For each seed, at the cell's own size: the control (the plain reference
computed in the precision below the configuration's, put in the
program's place) and each fault the cell can have, planted in the
reference, are read with the same compared numbers as the program, and
printed as one JSON line per seed with the cell's limits and which of
them each reading fails.  A training cell's "state left unchanged"
fault reads 1 on the weight change by construction and is not run.
The benchmark's own runs never run this; it runs on the chip by hand,
and at small sizes in ``bench/tests/test_control.py``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

from bench import harness


def faults_of(cell: harness.Cell) -> tuple[str, ...]:
    if cell.kind == "train":
        extra = ("no_exchange",) if cell.traffic["mesh"][0] > 1 else ()
        return ("half_batch", "altered") + extra
    return ("half_batch", "altered", "stale")


def upper(cell: harness.Cell, seed: int, devices) -> dict:
    """``{reading: {"numbers": {...}, "fails": [names over their limit]}}``."""
    system = importlib.import_module(f"bench.kinds.{cell.kind}")
    out = {}
    for name, numbers in system.upper_readings(
            cell, seed, devices, faults_of(cell)).items():
        ok, compared = harness.compare(numbers, cell.limits)
        out[name] = {"numbers": numbers,
                     "fails": [k for k, c in compared.items()
                               if not c["value"] <= c["limit"]]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    sys.path.insert(0, harness.SRC)
    os.makedirs(harness.CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("bench.control: JAX finds no TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "limits": cell.limits,
                          "readings": upper(cell, seed, devices[:1])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
