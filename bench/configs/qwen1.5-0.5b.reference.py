"""Plain reference of a Qwen1.5 (Qwen2 architecture) decoder and its training.

Straight ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: token embedding, per layer RMSNorm -> causal
multi-head attention with biases on q/k/v and rotary positions
(rotate-half, base ``rope_theta``) -> residual -> RMSNorm -> SiLU-gated
MLP -> residual, final RMSNorm, the tied embedding as the output head,
and the mean next-token cross-entropy.  Training follows the
configuration: each party's gradient (FedSGD) or pseudo-gradient after
``local_steps`` SGD steps on its microbatches (FedAvg), their mean over
the parties, then AdamW with global-norm clipping.

It imports nothing of the program and is given only what the benchmark
made from the seed: the initial weights (by a function that makes them)
and the token batches.  The weights use the program's tree of names,
read here by name.

``lower`` computes every matrix product from operands rounded to that
dtype (the control: a precision below the configuration's).  ``fault``
plants one of the faults the comparison has to catch, for the readings
that set its limits: ``"half_batch"`` (each party's loss and gradient
over the first half of its rows), ``"no_exchange"`` (the aggregated
gradient is party 0's alone, divided by the party count, as when the
shares never cross between parties), ``"altered"`` (one coordinate of
the aggregated gradient off by 16, the gradient codec's clip, as from a
flipped high bit of one share).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _round_to(x, lower):
    """``x`` rounded to dtype ``lower``; the gradient passes straight."""
    if lower is None:
        return x
    return x + jax.lax.stop_gradient(
        x.astype(lower).astype(jnp.float32) - x)


def _mm(a, b, lower, spec=None):
    a, b = _round_to(a, lower), _round_to(b, lower)
    if spec is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x: [B, H, S, Dh]; rotate-half rotary positions 0..S-1."""
    s, dh = x.shape[2], x.shape[3]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * \
        jnp.asarray(freqs, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def loss_fn(params, tokens, labels, model: dict, lower=None):
    """Mean next-token cross-entropy of ``tokens`` -> ``labels``."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hkv = model["num_key_value_heads"]
    dh = d // h
    eps = model["rms_norm_eps"]
    theta = model["rope_theta"]
    table = params["embed"]["table"]
    x = table[tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        blk = lp["b0_attn"]
        at = blk["attn"]
        y = _rmsnorm(x, blk["ln1"]["scale"], eps)
        q = _mm(y, at["wq"], lower) + at["bq"]
        k = _mm(y, at["wk"], lower) + at["bk"]
        v = _mm(y, at["wv"], lower) + at["bv"]
        q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, hkv, dh).transpose(0, 2, 1, 3)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = _mm(q, k, lower, "bhqd,bhkd->bhqk") / math.sqrt(dh)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = _mm(p, v, lower, "bhqk,bhkd->bhqd")
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
        x = x + _mm(o, at["wo"], lower)
        y = _rmsnorm(x, blk["ln2"]["scale"], eps)
        mlp = blk["mlp"]
        g = _mm(y, mlp["w_gate"], lower)
        u = _mm(y, mlp["w_up"], lower)
        x = x + _mm(jax.nn.silu(g) * u, mlp["w_down"], lower)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    logits = _mm(x, table.T, lower)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    """Per-leaf Euclidean norms (float32 accumulation)."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def leaf_diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def readings(make_params, batches, model: dict, optim: dict,
             traffic: dict, *, lower=None, fault=None, steps: int = 3):
    """Train ``steps`` steps from the seed's weights; the compared readings.

    ``batches[t]`` is step ``t``'s global ``(tokens, labels)`` pair, the
    rows of party ``p`` being ``p * rows .. (p + 1) * rows``.  Returns
    the per-step losses, the per-leaf norms of the first gradient as
    AdamW takes it (clipped; read from the first moment after step 1),
    and the per-leaf norms of the weights' change after ``steps`` steps.
    """
    n_party = traffic["mesh"][0]
    t_local = traffic["local_steps"]
    block = traffic["reference_rows"]
    inner_lr = traffic["inner_lr"]
    b1, b2 = optim["b1"], optim["b2"]
    lower = None if lower is None else jnp.dtype(lower)

    vg = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_fn(p, t, l, model, lower)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a))
    sgd = jax.jit(lambda p, g: jax.tree.map(
        lambda x, y: x - inner_lr * y, p, g))
    pseudo = jax.jit(lambda p, q: jax.tree.map(
        lambda x, y: (x - y) / inner_lr, p, q))

    @jax.jit
    def adamw(params, m, v, grads, step):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        c = jnp.minimum(1.0, optim["grad_clip"] / jnp.maximum(norm, 1e-12))
        t = step + 1.0
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def one(p, m_, v_, g):
            g = g * c
            m2 = b1 * m_ + (1.0 - b1) * g
            v2 = b2 * v_ + (1.0 - b2) * g * g
            upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + optim["eps"])
            return (p - optim["lr"] * (upd + optim["weight_decay"] * p),
                    m2, v2)

        out = jax.tree.map(one, params, m, v, grads)
        leaves = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        tdef = jax.tree.structure(params)
        return (jax.tree.unflatten(tdef, [o[0] for o in leaves]),
                jax.tree.unflatten(tdef, [o[1] for o in leaves]),
                jax.tree.unflatten(tdef, [o[2] for o in leaves]))

    def mean_grad(params, tokens, labels):
        """(loss, gradient) over the rows, in blocks of ``block`` rows so
        that the activations fit; every block has as many rows, so the
        mean over blocks is the mean over rows."""
        rows = tokens.shape[0]
        step = min(block, rows)
        if rows % step:
            raise ValueError(f"{rows} rows do not split into blocks of "
                             f"{step}")
        loss, grad = 0.0, None
        for lo in range(0, rows, step):
            l, g = vg(params, tokens[lo:lo + step], labels[lo:lo + step])
            loss += float(l)
            grad = g if grad is None else add(grad, g)
        n = rows // step
        return loss / n, (grad if n == 1 else scale(grad, 1.0 / n))

    def party_update(params, tokens, labels):
        """(loss, gradient or pseudo-gradient) of one party's rows."""
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        if t_local == 1:
            return mean_grad(params, tokens, labels)
        mb = tokens.shape[0] // t_local
        local, loss = params, 0.0
        for i in range(t_local):
            l, g = mean_grad(local, tokens[i * mb:(i + 1) * mb],
                             labels[i * mb:(i + 1) * mb])
            loss += l
            local = sgd(local, g)
        return loss / t_local, pseudo(params, local)

    params = make_params()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t in range(steps):
        tokens, labels = batches[t]
        rows = tokens.shape[0] // n_party
        loss, agg = 0.0, None
        for p in range(n_party):
            l, g = party_update(params, tokens[p * rows:(p + 1) * rows],
                                labels[p * rows:(p + 1) * rows])
            loss += l
            if fault == "no_exchange" and p > 0:
                continue
            agg = g if agg is None else add(agg, g)
        agg = scale(agg, 1.0 / n_party)
        if fault == "altered":
            first = jax.tree.leaves(agg)[0]
            agg = jax.tree.unflatten(
                jax.tree.structure(agg),
                [first.reshape(-1).at[0].add(16.0).reshape(first.shape)]
                + jax.tree.leaves(agg)[1:])
        losses.append(loss / n_party)
        params, m, v = adamw(params, m, v, agg, jnp.float32(t))
        del agg
        if t == 0:
            grad_norms = [float(x) / (1.0 - b1) for x in leaf_norms(m)]
    names = leaf_names(params)
    del m, v
    start = make_params()
    change = [float(x) for x in leaf_diff_norms(params, start)]
    return {"losses": losses,
            "grad_norms": dict(zip(names, grad_norms)),
            "change_norms": dict(zip(names, change))}
