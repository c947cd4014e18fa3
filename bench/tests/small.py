"""Cells of the benchmark at sizes a CPU test run holds.

Each is the named cell with its configuration cut to a toy: the same
kind module, traffic shape and comparison, on the CPU, kernels as their jnp
oracles.  The model computes in float32 here, so its gap to the float32
reference is rounding alone; its control is bfloat16.
"""

from __future__ import annotations

import copy

from bench import harness

#: Limits of the small training cells: float32 compute against the
#: float32 reference reads a loss gap of 0, a gradient gap of ~1e-7 and a
#: change gap of ~2.5e-4 (the gradient codec's rounding), the bfloat16
#: control ~6e-6, ~4e-4 to 1e-3 and ~6e-4 (on a CPU, two seeds).
SMALL_TRAIN_LIMITS = {"loss": 2e-6, "grad_norm": 1e-4, "change_norm": 2e-3}

SMALL_MODEL = {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "num_hidden_layers": 2, "vocab_size": 512}


def small_cell(workload: str, chips: int | None = None,
               **traffic) -> harness.Cell:
    """The cell at a small size; ``chips`` and ``traffic`` override the
    chips it asks for and keys of its mix."""
    cell = harness.resolve(harness.load_spec(), workload)
    cell = copy.deepcopy(cell)
    if chips is not None:
        cell.chips = chips
    if cell.kind == "train":
        cell.config["model"].update(SMALL_MODEL)
        cell.config["compute_dtype"] = "float32"
        cell.traffic.update(seq=16, pool_batches=4, trace_units=2,
                            reference_rows=4)
        cell.limits = dict(SMALL_TRAIN_LIMITS)
    else:
        cell.config.update(update_elems=(1 << 16) + (1 << 13),
                           chunk_elems=1 << 15)
        cell.traffic.update(pool_blocks=4, trace_units=2)
    cell.traffic.update(traffic)
    return cell
