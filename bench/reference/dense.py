"""Plain reference of a dense decoder's training step (GQA attention with
RoPE and an optional sliding window, RMSNorm, SwiGLU, untied head,
cross-entropy, AdamW with global-norm clipping and warmup-cosine decay).

Straightforward ``jax.numpy`` that imports nothing of the program: sizes
come from the configuration file, weights from the run's seed by the same
random draws the program's initializer makes (``normal / sqrt(fan_in)``
matrices, ``0.02 * normal`` embedding, unit norm scales).  Written for
float32 at ``highest`` matmul precision; the same code in bfloat16 at the
default precision is the control that has to fail.

To fit beside nothing else on the chips of the cell it runs a batch row by
row (the loss is the token mean, so per-row sums add up), checkpoints each
layer, takes attention a block of queries at a time (an exact softmax over
every key for each query), and shards each matrix over the cell's devices
on its larger axis.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

Q_BLOCK = 512
NORM_EPS = 1e-5


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def init(m: dict, key):
    """float32 weights from ``key``: the program's initializer, draw for
    draw."""
    d, f, v, h = m["d_model"], m["d_ff"], m["vocab_size"], m["head_dim"]
    q, kv = m["num_heads"] * h, m["num_kv_heads"] * h
    k_emb, k_trunk, k_head, _ = jax.random.split(key, 4)

    def lin(k, i, o):
        return {"w": _normal(k, (i, o), 1.0 / math.sqrt(i))}

    layers = []
    for lk in jax.random.split(k_trunk, m["num_layers"]):
        k_attn, k_mlp = jax.random.split(lk)
        ka = jax.random.split(k_attn, 6)
        km = jax.random.split(k_mlp, 3)
        layers.append({
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"q": lin(ka[0], d, q), "k": lin(ka[1], d, kv),
                     "v": lin(ka[2], d, kv), "o": lin(ka[3], q, d)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "mlp": {"gate": lin(km[0], d, f), "up": lin(km[1], d, f),
                    "down": lin(km[2], f, d)},
        })
    return {
        "embed": {"table": _normal(k_emb, (v, d), 0.02)},
        "trunk": {"dense_layers": jax.tree.map(lambda *a: jnp.stack(a),
                                               *layers)},
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
        "head": lin(k_head, d, v),
    }


def shardings(params, devices):
    """Each matrix sharded over ``devices`` on its larger trailing axis
    (when it divides), everything else replicated."""
    mesh = jax.sharding.Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def one(x):
        spec = [None] * x.ndim
        if x.ndim >= 2:
            ax = x.ndim - 1 if x.shape[-1] >= x.shape[-2] else x.ndim - 2
            if x.shape[ax] % n == 0:
                spec[ax] = "x"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, params), NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + NORM_EPS)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x (S, H, h): rotate the two halves of each head by the angle of
    each position."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(p, m, x):
    S = x.shape[0]
    H, KV, h = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(S)
    q = _rope((x @ p["q"]["w"]).reshape(S, H, h), pos, m["rope_theta"])
    k = _rope((x @ p["k"]["w"]).reshape(S, KV, h), pos, m["rope_theta"])
    v = (x @ p["v"]["w"]).reshape(S, KV, h)
    k = jnp.repeat(k, H // KV, axis=1)          # query head j reads kv j // G
    v = jnp.repeat(v, H // KV, axis=1)
    window = m["sliding_window"]
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(args):
        qblk, q0 = args
        qpos = q0 + jnp.arange(qb)
        s = jnp.einsum("qhd,khd->hqk", qblk, k).astype(jnp.float32)
        s = s / math.sqrt(h)
        ok = qpos[:, None] >= pos[None, :]
        if window:
            ok &= qpos[:, None] - pos[None, :] < window
        s = jnp.where(ok[None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("hqk,khd->qhd", w, v)

    blocks = q.reshape(S // qb, qb, H, h)
    out = jax.lax.map(block, (blocks, jnp.arange(S // qb) * qb))
    return out.reshape(S, H * h) @ p["o"]["w"]


def _layer(p, m, x):
    x = x + _attention(p["attn"], m, _rmsnorm(x, p["ln1"]["scale"]))
    hdn = _rmsnorm(x, p["ln2"]["scale"])
    mp = p["mlp"]
    return x + (jax.nn.silu(hdn @ mp["gate"]["w"]) * (hdn @ mp["up"]["w"])
                ) @ mp["down"]["w"]


def row_loss_sum(params, m, tokens, targets, mask):
    """Summed next-token cross-entropy of one row (S,)."""
    x = params["embed"]["table"][tokens]
    stack = params["trunk"]["dense_layers"]
    for i in range(m["num_layers"]):
        lp = jax.tree.map(lambda a: a[i], stack)
        x = jax.checkpoint(lambda q, v: _layer(q, m, v))(lp, x)
    x = _rmsnorm(x, params["ln_f"]["scale"])
    logits = (x @ params["head"]["w"]).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - tgt) * mask)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def lr_at(step: int, o: dict) -> float:
    warm = min(1.0, step / max(1, o["warmup"]))
    frac = min(1.0, max(0.0, (step - o["warmup"])
                        / max(1, o["total_steps"] - o["warmup"])))
    cos = o["floor"] + (1 - o["floor"]) * 0.5 * (1 + math.cos(math.pi * frac))
    return o["lr"] * warm * cos


def adamw(params, grads, mu, nu, count, lr, o):
    """One AdamW update with global-norm clipping, moments in float32.
    Returns the new (params, mu, nu) and the norms of the clipped
    gradients (``check.leaf_norms``)."""
    from bench.check import leaf_norms

    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    c1 = 1.0 - o["b1"] ** count
    c2 = 1.0 - o["b2"] ** count
    clipped = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)

    def one(p, g, m_, v_):
        m_ = o["b1"] * m_ + (1 - o["b1"]) * g
        v_ = o["b2"] * v_ + (1 - o["b2"]) * jnp.square(g)
        pf = p.astype(jnp.float32)
        step = (m_ / c1) / (jnp.sqrt(v_ / c2) + o["eps"])
        return (pf - lr * (step + o["weight_decay"] * pf)).astype(p.dtype), \
            m_, v_

    out = jax.tree.map(one, params, clipped, mu, nu)
    new = [jax.tree.map(lambda t, i=i: t[i], out,
                        is_leaf=lambda t: isinstance(t, tuple))
           for i in range(3)]
    return (*new, leaf_norms(clipped))


# ---------------------------------------------------------------------------
# the readings
# ---------------------------------------------------------------------------

def programs(m: dict, opt: dict, key, devices, dtype=jnp.float32):
    """The reference's jitted pieces for weights laid out over
    ``devices``: ``make(key)``, ``zeros()``, ``grad_row(params, acc,
    tokens, targets, mask, n) -> (row loss, acc + row grads)`` and
    ``update(params, grads, mu, nu, count, lr) -> (params, mu, nu, clipped
    grad norms)``, with the shardings."""
    shapes = jax.eval_shape(partial(init, m), key)
    p_shard, rep = shardings(shapes, devices)
    make = jax.jit(lambda k: jax.tree.map(lambda a: a.astype(dtype),
                                          init(m, k)),
                   out_shardings=p_shard)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.float32), shapes),
        out_shardings=p_shard)

    def acc_row(p, acc, t, y, w, n):
        loss, g = jax.value_and_grad(
            lambda q: row_loss_sum(q, m, t, y, w) / n)(p)
        return loss, jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, g)

    grad_row = jax.jit(acc_row, donate_argnums=1,
                       out_shardings=(rep, p_shard))
    update = jax.jit(partial(adamw, o=opt), donate_argnums=(0, 2, 3),
                     out_shardings=(p_shard, p_shard, p_shard, None))
    return {"make": make, "zeros": zeros, "grad_row": grad_row,
            "update": update, "shapes": shapes, "p_shard": p_shard,
            "rep": rep}


def train_readings(m: dict, opt: dict, key, batches, devices, *,
                   dtype=jnp.float32, precision="highest",
                   rows: slice = slice(None)):
    """Run ``len(batches)`` training steps from the weights of ``key`` and
    return ``{"losses", "grad", "change"}``: each step's loss, each leaf's
    first clipped gradient norm, and each leaf's change over the steps
    (``check.flatten`` names).  ``rows`` keeps only some rows of every
    batch (a fault: half of the batch left out)."""
    from bench.check import diff_norms, flatten

    with jax.default_matmul_precision(precision):
        f = programs(m, opt, key, devices, dtype)
        rep = f["rep"]
        params, mu, nu = f["make"](key), f["zeros"](), f["zeros"]()
        losses, first_grad = [], None
        for step, b in enumerate(batches):
            toks, tgts, mask = (np.asarray(b[k])[rows]
                                for k in ("tokens", "targets", "mask"))
            n = jnp.float32(mask.sum())
            loss, grads = 0.0, f["zeros"]()
            for r in range(toks.shape[0]):
                row_loss, grads = f["grad_row"](
                    params, grads, *(jax.device_put(a[r], rep)
                                     for a in (toks, tgts, mask)), n)
                loss += float(row_loss)
            params, mu, nu, gn = f["update"](params, grads, mu, nu,
                                             jnp.float32(step + 1),
                                             jnp.float32(lr_at(step, opt)))
            del grads
            if first_grad is None:
                first_grad = flatten(jax.device_get(gn))
            losses.append(loss)
        del mu, nu
        change = flatten(jax.device_get(jax.jit(diff_norms)(
            params, f["make"](key))))
    return {"losses": losses, "grad": first_grad, "change": change}
