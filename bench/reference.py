"""The plain reference: the configuration's decoder in straightforward
``jax.numpy``, float32 at ``precision=HIGHEST``, with no kernels, cache or
batching of requests, and AdamW written out.  It imports nothing of the
program.  Weights come from ``bench/model.init_tree`` with the run's
seed, the same bfloat16 values the program was given, upcast.

The layer: pre-norm RMSNorm scaled by ``1 + w``; grouped-query attention
with optional RMSNorm of q and k over the head; rotary embedding on the
whole head, halves rotated, frequencies ``theta ** (-i / (hd / 2))``;
causal softmax at ``1 / sqrt(hd)``; SwiGLU MLP ``wo(silu(x wg) * x wi)``;
a final RMSNorm and the (tied or separate) head.  Training loss: mean
cross entropy plus ``z_loss`` times the mean squared log-partition.

``mode`` selects the arithmetic of the dense matrix products:
``"f32"`` is the reference; ``"int8"`` is the control, int8 training's
products in both passes: every operand quantized to int8 with an absmax
scale per row or column of the contraction, in the forward product and
in the two products of its gradient.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import dims

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s), s


def _mm8(a, b):
    """``a [..., k] @ b [k, n]``, ``a`` quantized per row and ``b`` per
    column; integer values up to 127 are exact in one bfloat16 pass."""
    qa, sa = _q8(a, -1)
    qb, sb = _q8(b, 0)
    return jnp.einsum("...k,kn->...n", qa, qb) * sa * sb


@jax.custom_vjp
def _dense8(x, w):
    return _mm8(x, w)


def _dense8_fwd(x, w):
    return _mm8(x, w), (x, w)


def _dense8_bwd(res, dy):
    """Both products of the gradient in int8 too: ``dy`` per token
    against ``w`` per input row, and ``x`` per feature against ``dy`` per
    output column, each over the tokens."""
    x, w = res
    dx = _mm8(dy, w.T)
    x2 = x.reshape(-1, x.shape[-1])
    dw = _mm8(x2.T, dy.reshape(-1, dy.shape[-1]))
    return dx, dw


_dense8.defvjp(_dense8_fwd, _dense8_bwd)


def dense(x, w, mode: str):
    if mode == "int8":
        return _dense8(x, w)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, pos, theta):
    """x [T, H, hd], pos [T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(q, k, v, q0, chunk):
    """Causal attention of queries at positions ``q0..q0+len(q)`` over
    keys ``0..len(k)``; q [C, nh, hd], k/v [T, nkv, hd]."""
    C, nh, hd = q.shape
    g = nh // k.shape[1]
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HI) / math.sqrt(hd)
    qp = q0 + jnp.arange(C)
    mask = jnp.arange(k.shape[0])[None, :] <= qp[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)


def layer(p, x, c: dict, mode: str, chunk: int = 512):
    """One decoder layer over one sequence x [T, d] (f32 weights ``p``)."""
    k = dims(c)
    T = x.shape[0]
    nh, nkv, hd = k["nh"], k["nkv"], k["hd"]
    pos = jnp.arange(T)
    h = rmsnorm(x, p["ln1"], k["eps"])
    a = p["attn"]
    q = dense(h, a["wq"], mode).reshape(T, nh, hd)
    kk = dense(h, a["wk"], mode).reshape(T, nkv, hd)
    v = dense(h, a["wv"], mode).reshape(T, nkv, hd)
    if k["qk_norm"]:
        q = rmsnorm(q, a["q_norm"], k["eps"])
        kk = rmsnorm(kk, a["k_norm"], k["eps"])
    q, kk = rope(q, pos, k["theta"]), rope(kk, pos, k["theta"])
    C = min(chunk, T)
    outs = [jax.checkpoint(partial(_attend, q0=i, chunk=C))(
        q[i:i + C], kk[:i + C], v[:i + C]) for i in range(0, T, C)]
    o = jnp.concatenate(outs, 0).reshape(T, nh * hd)
    x = x + dense(o, a["wo"], mode)
    h = rmsnorm(x, p["ln2"], k["eps"])
    m = p["mlp"]
    return x + dense(jax.nn.silu(dense(h, m["wg"], mode))
                     * dense(h, m["wi"], mode), m["wo"], mode)


def head(params, c: dict):
    return params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]


def logits(params, tokens, c: dict, mode: str):
    """[T, V] logits of one sequence; every layer rematerialized."""
    k = dims(c)
    x = params["embed"][tokens]
    seg = params["segments"][0]
    for i in range(k["L"]):
        lp = jax.tree.map(lambda a: a[i], seg)
        x = jax.checkpoint(partial(layer, c=c, mode=mode))(lp, x)
    x = rmsnorm(x, params["final_norm"], k["eps"])
    return dense(x, head(params, c), mode)


def seq_loss(params, inputs, targets, c: dict, z_loss: float, mode: str):
    """(mean cross entropy + z-loss) of one sequence."""
    lg = logits(params, inputs, c, mode)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - tgt) + z_loss * jnp.mean(lse * lse)


# ---------------------------------------------------------------------------
# Training: three steps of clipped AdamW
# ---------------------------------------------------------------------------

def lr_at(a: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_frac`` of ``lr``."""
    warm = min(1.0, (step + 1) / max(a["warmup_steps"], 1))
    prog = min(max((step - a["warmup_steps"])
                   / max(a["total_steps"] - a["warmup_steps"], 1), 0.0), 1.0)
    frac = a["min_lr_frac"] + (1 - a["min_lr_frac"]) \
        * 0.5 * (1 + math.cos(math.pi * prog))
    return a["lr"] * warm * frac


def train_readings(c: dict, tw: dict, make_tree, batches, mesh,
                   mode: str = "f32", fault: str = "",
                   sample=None, diagnose: bool = False) -> dict:
    """The reference's readings over three steps of ``batches`` (numpy
    ``inputs``/``targets`` [B, T]): each step's loss, each leaf's norm of
    the first clipped gradient and, at the flat indices ``sample`` (one
    array a leaf), its elements; each leaf's norm of the master weights'
    change after three steps (``diagnose``: after every step).
    ``make_tree()`` gives the bfloat16 weights.

    ``fault == "half"`` plants a training step's fault in the reference:
    the second half of each batch left out.  ``mesh`` is a 1-D mesh over
    the cell's chips: the weights, moments and gradients are sharded over
    it, and the rows run ``n`` at a time, one on each chip, each
    rematerialized."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    ax = mesh.axis_names[0]
    a = tw["adamw"]

    def spec(x):
        best = max((i for i in range(x.ndim) if x.shape[i] % n == 0),
                   key=lambda i: x.shape[i], default=None)
        s = [None] * x.ndim
        if best is not None and n > 1:
            s[best] = ax
        return NamedSharding(mesh, P(*s))

    tree = make_tree()
    shard = jax.tree.map(spec, tree)
    f32 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t),
                  out_shardings=shard)
    w = f32(tree)
    del tree
    z_loss = tw["z_loss"]
    rows = NamedSharding(mesh, P(None, ax))
    idx = [jnp.asarray(i) for i in sample] if sample is not None else \
        [jnp.zeros(0, jnp.int32) for _ in jax.tree.leaves(w)]

    @partial(jax.jit, out_shardings=(None, shard))
    def batch_grad(p, inputs, targets):
        """Mean loss and gradient over rows [blocks, n, T]."""
        def total(p):
            one = jax.checkpoint(
                lambda i, t: seq_loss(p, i, t, c, z_loss, mode))

            def block(xy):
                return jnp.sum(jax.vmap(one)(*xy))
            return jnp.sum(jax.lax.map(block, (inputs, targets)))
        k = inputs.shape[0] * inputs.shape[1]
        loss, g = jax.value_and_grad(total)(p)
        return loss / k, jax.tree.map(lambda x: x / k, g)

    def grads(p, inputs, targets):
        shape = (-1, n, inputs.shape[-1])
        loss, g = batch_grad(
            p, jax.device_put(inputs.reshape(shape), rows),
            jax.device_put(targets.reshape(shape), rows))
        return float(loss), g

    @partial(jax.jit, donate_argnums=(0, 2, 3), out_shardings=(
        shard, shard, shard, None))
    def adamw(w, g, m, v, lr, t, idx):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, tw["clip_norm"] / (gn + 1e-9)) \
            if tw["clip_norm"] > 0 else jnp.float32(1.0)
        g = jax.tree.map(lambda x: x * scale, g)
        m = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g, m, g)
        v = jax.tree.map(lambda v, g: a["b2"] * v + (1 - a["b2"]) * g * g,
                         v, g)

        def upd(w, m, v):
            mh = m / (1 - a["b1"] ** t)
            vh = v / (1 - a["b2"] ** t)
            return w - lr * (mh / (jnp.sqrt(vh) + a["eps"])
                             + a["weight_decay"] * w)
        w = jax.tree.map(upd, w, m, v)
        leaves = jax.tree.leaves(g)
        return w, m, v, ([jnp.linalg.norm(x) for x in leaves],
                         [x.reshape(-1)[i] for x, i in zip(leaves, idx)])

    change = jax.jit(lambda w, t: [
        jnp.linalg.norm(x - y.astype(jnp.float32))
        for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(t))])
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=shard)
    m, v = zeros(w), zeros(w)
    losses, g_norms, g_sample, by_step = [], None, None, []
    for s, b in enumerate(batches[:3]):
        x, y = b["inputs"], b["targets"]
        if fault == "half":
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        loss, g = grads(w, x, y)
        losses.append(loss)
        w, m, v, (gn, gs) = adamw(w, g, m, v, jnp.float32(lr_at(a, s)),
                                  jnp.float32(s + 1), idx)
        del g
        if s == 0:
            g_norms = [float(x) for x in gn]
            g_sample = [np.asarray(x) for x in gs]
        if diagnose or s == 2:
            by_step.append([float(x) for x in change(w, make_tree())])
    out = {"losses": losses, "grad_norms": g_norms, "grad_sample": g_sample,
           "change_norms": by_step[-1]}
    if diagnose:
        out["change_by_step"] = by_step
    return out


# ---------------------------------------------------------------------------
# Serving: logits of prompt + served tokens
# ---------------------------------------------------------------------------

def serve_gaps(c: dict, tree, seqs, length: int, mode: str = "f32"):
    """For each (prompt, served tokens): at every served position, how far
    the f32 reference's logit of the token (the served one; with
    ``mode="int8"``, the one the int8 control puts first) lies below the
    reference's best, in standard deviations of the reference's logits at
    that position (a scale that does not depend on the model's size).

    Layer by layer, all sequences at once, each padded to ``length``
    (causal: padding after a position does not reach it), so that every
    call runs the same compiled programs."""
    k = dims(c)
    toks = np.zeros((len(seqs), length), np.int32)
    served = np.zeros((len(seqs), length), np.int32)
    for i, (p, g) in enumerate(seqs):
        s = np.concatenate([p, np.asarray(g[:-1], np.int32)])
        toks[i, :len(s)] = s
        served[i, len(p) - 1:len(p) - 1 + len(g)] = g

    f32 = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t))
    run_layer = jax.jit(jax.vmap(partial(layer, c=c, mode="f32"),
                                 in_axes=(None, 0)))
    run_ctrl = jax.jit(jax.vmap(partial(layer, c=c, mode=mode),
                                in_axes=(None, 0)))
    seg = tree["segments"][0]
    x = f32(tree["embed"])[jnp.asarray(toks)]
    xc = x
    for i in range(k["L"]):
        lp = f32(jax.tree.map(lambda a: a[i], seg))
        x = run_layer(lp, x)
        if mode != "f32":
            xc = run_ctrl(lp, xc)
    top = {"final_norm": tree["final_norm"], "embed": tree["embed"]}
    if not c["tie_word_embeddings"]:
        top["lm_head"] = tree["lm_head"]

    @jax.jit
    def gaps(top, x, xc, served):
        top = jax.tree.map(lambda a: a.astype(jnp.float32), top)
        W = head(top, c)
        ref = dense(rmsnorm(x, top["final_norm"], k["eps"]), W, "f32")
        if mode == "f32":
            pick = served
        else:
            hc = rmsnorm(xc, top["final_norm"], k["eps"])
            pick = jnp.argmax(dense(hc, W, mode), axis=-1)
        return (jnp.max(ref, -1) - jnp.take_along_axis(
            ref, pick[:, None], -1)[:, 0]) / jnp.std(ref, -1)

    out = []
    for i, (p, g) in enumerate(seqs):
        row = np.asarray(gaps(top, x[i], xc[i], jnp.asarray(served[i])))
        out.append(row[len(p) - 1:len(p) - 1 + len(g)])
    return out
