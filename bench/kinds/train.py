"""A training cell: the program's secure-aggregation train step.

Set-up builds one object, the jitted step of
``repro.launch.steps.make_train_step(...)`` -> ``wrap(batch_specs)`` with
its weights and AdamW state made on the device from the seed, drives it
through the first ``check_steps`` steps (which compile it, and whose
losses, first gradient and weight change are read for the comparison),
and hands the same object to the window.  The window is a closed loop of
steps, each ending when its loss reaches the host, as
``repro.launch.train.run`` times them.  The token batches are a pool made
on the device at set-up, drawn over the whole vocabulary, which the
steps cycle through; the step indices are placed on the device at
set-up too, so the window uploads nothing.
"""

from __future__ import annotations

import contextlib
import statistics
import sys
import time

import numpy as np

from bench import harness

#: Share of the median leaf's reference gradient norm below which a leaf
#: is "nought to rounding" (a key bias under softmax) and its change is
#: not compared: Adam moves such a leaf by round-off alone.
STILL_LEAF = 1e-3

#: Seed of the program's mask streams.  The step takes it as a
#: compile-time constant, so a seed drawn from ``--seed`` would compile
#: the step anew in every run; the additive masks cancel exactly, so the
#: step's result does not depend on it.
MASK_SEED = 0x5EC0A66

#: Fastest step the window prepares indices for (5 ms: a third of the
#: time the chip's bf16 peak needs for one 8 x 128 step of a 0.46B model,
#: the smallest batch a cell has used); past them the indices start again
#: from the window's first.
MIN_STEP_S = 0.005


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """``n`` uint32 words from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def key_of(seed: int, stream: int):
    import jax

    w = seed_words(seed, 8)
    return jax.random.wrap_key_data(
        np.array([w[2 * stream], w[2 * stream + 1]], np.uint32))


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a dense decoder configuration."""
    from repro.models.common import ArchConfig

    m = config["model"]
    h = m["num_attention_heads"]
    return ArchConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=h, n_kv_heads=m["num_key_value_heads"],
        d_head=m["hidden_size"] // h, d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], rope="rope", rope_theta=m["rope_theta"],
        qkv_bias=True, tie_embeddings=m["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=m["rms_norm_eps"], act="silu",
        param_dtype="float32", compute_dtype=config["compute_dtype"])


def make_params(key, model: dict):
    """Seeded weights in the program's tree of names (HF initialisation:
    normal(0, initializer_range) matrices, zero biases, unit norms)."""
    import jax
    import jax.numpy as jnp

    d, f, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    layers = model["num_hidden_layers"]
    h, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    dh = d // h
    std = model["initializer_range"]
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * std

    ones = lambda *s: jnp.ones(s, jnp.float32)      # noqa: E731
    zeros = lambda *s: jnp.zeros(s, jnp.float32)    # noqa: E731
    return {
        "embed": {"table": normal(ks[0], (v, d))},
        "layers": {"b0_attn": {
            "ln1": {"scale": ones(layers, d)},
            "attn": {"wq": normal(ks[1], (layers, d, h * dh)),
                     "wk": normal(ks[2], (layers, d, hkv * dh)),
                     "wv": normal(ks[3], (layers, d, hkv * dh)),
                     "wo": normal(ks[4], (layers, h * dh, d)),
                     "bq": zeros(layers, h * dh),
                     "bk": zeros(layers, hkv * dh),
                     "bv": zeros(layers, hkv * dh)},
            "ln2": {"scale": ones(layers, d)},
            "mlp": {"w_gate": normal(ks[5], (layers, d, f)),
                    "w_up": normal(ks[6], (layers, d, f)),
                    "w_down": normal(ks[7], (layers, f, d))}}},
        "final_norm": {"scale": ones(d)},
    }


def make_pool(key, vocab: int, pool: int, batch: int, seq: int):
    """``pool`` batches of ``(tokens, labels)``, uniform over the vocabulary."""
    import jax

    toks = jax.random.randint(key, (pool, batch, seq + 1), 0, vocab)
    return tuple({"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                 for i in range(pool))


def work_counts(cell) -> dict:
    model, tr = cell.config["model"], cell.traffic
    n_party = tr["mesh"][0]
    rows = tr["micro_batch"] * tr["local_steps"] * n_party
    flops = harness.load_work("dense_lm_train_flops").flops_per_token(
        model, tr["seq"])
    sec = harness.load_work("secure_agg_bytes").bytes_per_party_step(
        harness.load_work("dense_lm_params").count(model), tr["m"])
    return {"tokens_per_step": rows * tr["seq"], "flops_per_token": flops,
            "secure_agg_bytes_per_party_step": sec, "parties": n_party,
            "unit": "step"}


class Session:
    """The compiled step with its state, from set-up to the comparison."""

    def __init__(self, cell, seed: int, devices, log, seconds: float,
                 trace_units: int | None = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.steps import make_train_step
        from repro.optim import AdamWConfig, adamw_init

        self.cell, self.seed = cell, seed
        self.phases, t = {}, time.perf_counter()
        cfg, tr = cell.config, cell.traffic
        self.model, self.optim = cfg["model"], cfg["optim"]
        n_party, tp = tr["mesh"]
        if n_party * tp != len(devices):
            raise harness.BenchError(
                f"mesh {tr['mesh']} needs {n_party * tp} chips, the cell "
                f"has {len(devices)}")
        self._ctx = contextlib.ExitStack()
        mesh = jax.make_mesh((n_party, tp), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2,
                             devices=devices)
        self._ctx.enter_context(jax.set_mesh(mesh))
        rows = tr["micro_batch"] * tr["local_steps"] * n_party
        seq = tr["seq"]
        specs = {"tokens": jax.ShapeDtypeStruct((rows, seq), np.int32),
                 "labels": jax.ShapeDtypeStruct((rows, seq), np.int32)}
        opt = AdamWConfig(**self.optim)
        wrap, abstract, _ = make_train_step(
            arch_config(cfg), mesh, protocol=tr["protocol"],
            scheme=tr["scheme"], m=tr["m"], agg_mode=tr["agg_mode"],
            seed=MASK_SEED, fsdp=False, opt=opt,
            local_steps=tr["local_steps"], inner_lr=tr["inner_lr"])
        self.step, shard = wrap(specs)
        self.phases["build"], t = time.perf_counter() - t, time.perf_counter()

        init = jax.jit(lambda k: make_params(k, self.model),
                       out_shardings=shard["params"])
        want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
        got = jax.tree.map(lambda a: (a.shape, a.dtype),
                           jax.eval_shape(init, key_of(seed, 0)))
        if want != got:
            raise harness.BenchError("the benchmark's weights do not match "
                                     "the program's parameter tree")
        self.params = init(key_of(seed, 0))
        self.opt = jax.jit(adamw_init, out_shardings=shard["opt"])(
            self.params)
        jax.block_until_ready((self.params, self.opt))
        self.phases["weights"], t = time.perf_counter() - t, time.perf_counter()
        pool = tr["pool_batches"]
        self.batches = jax.jit(
            lambda k: make_pool(k, self.model["vocab_size"], pool, rows, seq),
            out_shardings=(shard["batch"],) * pool)(key_of(seed, 1))
        jax.block_until_ready(self.batches)
        self.phases["batches"], t = time.perf_counter() - t, time.perf_counter()
        check = tr["check_steps"]
        units = trace_units if trace_units is not None else \
            int(seconds / MIN_STEP_S) + 1
        self.idx = jax.device_put(
            [np.int32(i) for i in range(check + units)],
            NamedSharding(mesh, P()))
        self.work = work_counts(cell)
        jax.block_until_ready(self.idx)
        self.phases["indices"], t = time.perf_counter() - t, time.perf_counter()

        # the first steps: compile, and read what the reference checks
        ref = harness.load_module(cell.reference_path)
        self.losses = []
        for step_t in range(check):
            self.params, self.opt, loss = self.step(
                self.params, self.opt, self.idx[step_t],
                self.batches[step_t % pool])
            self.losses.append(float(loss))
            if step_t == 0:
                self.phases["first_step"], t = (time.perf_counter() - t,
                                                time.perf_counter())
                self.grad_norms = [float(x) / (1.0 - self.optim["b1"])
                                   for x in ref.leaf_norms(self.opt["m"])]
        start = init(key_of(seed, 0))
        self.change_norms = [float(x) for x in
                             ref.leaf_diff_norms(self.params, start)]
        self.names = ref.leaf_names(self.params)
        del start
        self.next = check
        self.phases["later_check_steps"] = time.perf_counter() - t
        leaves = len(jax.tree.leaves(abstract))
        log.require_compiled("set-up", {k: leaves
                                        for k in tr["calls_per_leaf"]})

    def window(self, seconds: float, units: int | None = None):
        """Closed loop of steps for ``seconds`` (or exactly ``units``).

        Returns each step's host-clock duration, the window's length and
        the count of steps whose loss was not finite.
        """
        import jax

        pool, n_idx = len(self.batches), len(self.idx)
        check = self.cell.traffic["check_steps"]
        durs, failed = [], 0
        i = self.next
        t0 = end = time.perf_counter()
        while (len(durs) < units) if units is not None else \
                (time.perf_counter() - t0 < seconds):
            ts = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("bench.step", step_num=i):
                with jax.profiler.TraceAnnotation("program.train_step"):
                    self.params, self.opt, loss = self.step(
                        self.params, self.opt,
                        self.idx[check + (i - check) % (n_idx - check)],
                        self.batches[i % pool])
                with jax.profiler.TraceAnnotation("bench.loss_to_host"):
                    value = float(loss)
            end = time.perf_counter()
            durs.append(end - ts)
            failed += not np.isfinite(value)
            i += 1
        self.next = i
        return durs, end - t0, failed

    def release(self):
        """Free the program's state before the reference runs."""
        del self.params, self.opt, self.batches, self.idx, self.step
        self._ctx.close()

    def compared(self, devices) -> dict:
        """Program vs plain reference over the first steps."""
        ref = reference_readings(self.cell, self.seed, devices[0])
        prog = {"losses": self.losses,
                "grad_norms": dict(zip(self.names, self.grad_norms)),
                "change_norms": dict(zip(self.names, self.change_norms))}
        return gaps(prog, ref)


def reference_readings(cell, seed: int, device, **kw) -> dict:
    """The reference's readings, from the seed's weights and batches."""
    import jax

    tr = cell.traffic
    ref = harness.load_module(cell.reference_path)
    model = cell.config["model"]
    rows = tr["micro_batch"] * tr["local_steps"] * tr["mesh"][0]
    one = jax.sharding.SingleDeviceSharding(device)
    init = jax.jit(lambda k: make_params(k, model), out_shardings=one)
    pool = jax.jit(lambda k: make_pool(k, model["vocab_size"],
                                       tr["pool_batches"], rows, tr["seq"]),
                   out_shardings=one)(key_of(seed, 1))
    check = tr["check_steps"]
    batches = [(pool[t % len(pool)]["tokens"], pool[t % len(pool)]["labels"])
               for t in range(check)]
    with jax.default_device(device):
        return ref.readings(lambda: init(key_of(seed, 0)), batches, model,
                            cell.config["optim"], tr, steps=check, **kw)


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers: each a gap from the reference.

    ``loss``: the largest relative gap of a step's loss.  ``grad_norm``
    and ``change_norm``: by the worst leaf, the gap between the two
    norms over the reference's norm of that leaf or of the median leaf,
    whichever is larger; the change leaves out leaves whose reference
    gradient is under ``STILL_LEAF`` of the median leaf's.
    """
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    gr, cr = ref["grad_norms"], ref["change_norms"]
    med_g = statistics.median(gr.values())
    grad = max(abs(prog["grad_norms"][k] - gr[k]) / max(gr[k], med_g)
               for k in gr)
    moved = [k for k in gr if gr[k] >= STILL_LEAF * med_g]
    if len(moved) < len(gr):
        print("change_norm leaves out", sorted(set(gr) - set(moved)),
              file=sys.stderr)
    med_c = statistics.median(cr[k] for k in moved)
    change = max(abs(prog["change_norms"][k] - cr[k]) / max(cr[k], med_c)
                 for k in moved)
    return {"loss": loss, "grad_norm": grad, "change_norm": change}


def upper_readings(cell, seed: int, devices, faults) -> dict:
    """Control and planted faults, each in the program's place."""
    ref = reference_readings(cell, seed, devices[0])
    lower = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}[
        cell.config["compute_dtype"]]
    out = {"control": gaps(reference_readings(
        cell, seed, devices[0], lower=lower), ref)}
    for fault in faults:
        out[fault] = gaps(reference_readings(cell, seed, devices[0],
                                             fault=fault), ref)
    return out
