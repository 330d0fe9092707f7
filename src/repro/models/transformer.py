"""Decoder LM backbone: pattern-segmented layer stack, scan + remat.

The stack is described by a *layer pattern* — one block descriptor per
layer — segmented into maximal runs of identical descriptors.  Each
segment's parameters are stacked on a leading axis and applied with
``lax.scan`` (optionally ``jax.checkpoint``-rematerialized), keeping the
HLO small (one body per segment) for the 512-device dry-run.  This
uniformly covers:

  * homogeneous stacks (mixtral/qwen3/gemma-7b/phi4/musicgen/pixtral),
  * gemma3's 5:1 local:global attention pattern,
  * xLSTM's mLSTM/sLSTM mix,
  * zamba2's Mamba2 runs with a *shared* (weight-tied) attention block
    applied between segments.

Decode state mirrors the segment structure: each segment carries stacked
per-layer caches (KV for attention, conv/ssm for Mamba2, C/n/m for
mLSTM, c/n/h/m for sLSTM).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import layers as L
from . import moe as M
from . import ssm as S
from .sharding import seq_sharded, shard


# ---------------------------------------------------------------------------
# Layer patterns
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Block:
    kind: str                      # attn | moe | mamba2 | mlstm | slstm | shared_attn
    window: Optional[int] = None   # sliding-window size for attn kinds


def layer_pattern(cfg) -> List[Block]:
    """One Block per layer, in depth order."""
    n = cfg.n_layers
    if cfg.block_pattern == "xlstm":
        # xLSTM[a:b]-style mix: sLSTM every 4th block, mLSTM otherwise.
        return [Block("slstm") if (i % 4 == 3) else Block("mlstm")
                for i in range(n)]
    if cfg.block_pattern == "zamba":
        # Mamba2 backbone; the *shared* attention block fires after every
        # cfg.attn_every Mamba blocks (weight-tied across firings).
        out: List[Block] = []
        for i in range(n):
            out.append(Block("mamba2"))
            if cfg.attn_every and (i + 1) % cfg.attn_every == 0:
                out.append(Block("shared_attn"))
        return out
    kind = "moe" if cfg.n_experts > 0 else "attn"
    if cfg.local_global_ratio > 0:
        # k local (windowed) layers per 1 global, gemma3-style.
        k = cfg.local_global_ratio
        out = []
        for i in range(n):
            if (i + 1) % (k + 1) == 0:
                out.append(Block(kind, window=None))
            else:
                out.append(Block(kind, window=cfg.local_window))
        return out
    return [Block(kind, window=cfg.window) for _ in range(n)]


def segments(cfg) -> List[Tuple[Block, int]]:
    """Maximal runs of identical blocks: [(block, run_length), ...]."""
    pat = layer_pattern(cfg)
    out: List[Tuple[Block, int]] = []
    for b in pat:
        if out and out[-1][0] == b and b.kind != "shared_attn":
            out[-1] = (b, out[-1][1] + 1)
        else:
            out.append((b, 1))
    return out


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_block(key, cfg, block: Block) -> dict:
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    if block.kind in ("attn", "moe", "shared_attn"):
        p = {
            "ln1": L.init_rmsnorm(d, dt),
            "attn": L.init_attention(ks[0], cfg),
            "ln2": L.init_rmsnorm(d, dt),
        }
        if block.kind == "moe":
            p["moe"] = M.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
        return p
    if block.kind == "mamba2":
        p = {"ln1": L.init_rmsnorm(d, dt), "mamba": S.init_mamba2(ks[0], cfg)}
        # zamba2: Mamba blocks carry no FFN — d_ff belongs to the shared block
        if cfg.block_pattern != "zamba":
            p["ln2"] = L.init_rmsnorm(d, dt)
            p["mlp"] = L.init_mlp(ks[1], cfg)
        return p
    if block.kind == "mlstm":
        return {"ln1": L.init_rmsnorm(d, dt), "mlstm": S.init_mlstm(ks[0], cfg)}
    if block.kind == "slstm":
        return {"ln1": L.init_rmsnorm(d, dt), "slstm": S.init_slstm(ks[0], cfg)}
    raise ValueError(block.kind)


def init_params(key, cfg) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    if cfg.frontend is None:
        params["embed"] = (jax.random.normal(
            ks[0], (cfg.vocab_size, d), jnp.float32) * 0.02).astype(dt)
    else:
        # modality frontend STUB: precomputed frame/patch embeddings enter
        # through a trainable projection (the backbone is the deliverable).
        params["frontend_proj"] = L.init_dense(ks[0], cfg.frontend_dim, d, dt)
        params["embed"] = (jax.random.normal(
            ks[5], (cfg.vocab_size, d), jnp.float32) * 0.02).astype(dt)
    segs = []
    for si, (block, n) in enumerate(segments(cfg)):
        if block.kind == "shared_attn":
            segs.append({})  # weight-tied: params live in params["shared"]
            continue
        bks = jax.random.split(jax.random.fold_in(ks[1], si), n)
        stacked = jax.vmap(lambda k: _init_block(k, cfg, block))(bks)
        segs.append(stacked)
    params["segments"] = segs
    if any(b.kind == "shared_attn" for b, _ in segments(cfg)):
        params["shared"] = _init_block(ks[2], cfg, Block("shared_attn"))
    params["final_norm"] = L.init_rmsnorm(d, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(ks[3], d, cfg.vocab_size, dt)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _apply_block(p, cfg, block: Block, x, positions):
    """One layer forward.  Returns (x', aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if block.kind in ("attn", "moe", "shared_attn"):
        h = L.attention(p["attn"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                        positions, window=block.window)
        x = x + h
        y = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if block.kind == "moe":
            m, aux = M.moe(p["moe"], cfg, y)
        else:
            m = L.mlp(p["mlp"], cfg, y)
        x = x + m
    elif block.kind == "mamba2":
        x = x + S.mamba2(p["mamba"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps))
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], cfg, L.rmsnorm(x, p["ln2"], cfg.norm_eps))
    elif block.kind == "mlstm":
        x = x + S.mlstm(p["mlstm"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps))
    elif block.kind == "slstm":
        x = x + S.slstm(p["slstm"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps))
    else:
        raise ValueError(block.kind)
    return seq_sharded(x), aux


@jax.named_scope("embed")
def _embed_in(params, cfg, inputs):
    if cfg.frontend is not None and inputs.ndim == 3:
        x = L.dense(inputs, params["frontend_proj"])
    else:
        emb = shard(params["embed"], "model", None)
        x = jnp.take(emb, inputs, axis=0)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return seq_sharded(x)


def forward(params, cfg, inputs, positions=None) -> Tuple[jax.Array, jax.Array]:
    """inputs: [B,T] int tokens or [B,T,frontend_dim] float embeddings.

    Returns (logits [B,T,V], aux_loss scalar)."""
    B, T = inputs.shape[:2]
    if positions is None:
        positions = jnp.arange(T, dtype=jnp.int32)
    x = _embed_in(params, cfg, inputs)
    aux_total = jnp.zeros((), jnp.float32)

    for (block, n), seg_p in zip(segments(cfg), params["segments"]):
        if block.kind == "shared_attn":
            x, aux = _apply_block(params["shared"], cfg, block, x, positions)
            aux_total = aux_total + aux
            continue

        def body(carry, lp):
            h, acc = carry
            h, aux = _apply_block(lp, cfg, block, h, positions)
            return (h, acc + aux), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_total), _ = lax.scan(fn, (x, aux_total), seg_p)

    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        head = shard(head, None, "model")
        logits = jnp.einsum("btd,dv->btv", x, head)
        logits = shard(logits, None, None, "model")
    return logits, aux_total


def loss_fn(params, cfg, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: dict(inputs [B,T] or [B,T,F], targets [B,T], mask [B,T]).

    Cross entropy in fp32 with z-loss; returns (loss, metrics)."""
    logits, aux = forward(params, cfg, batch["inputs"])
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, batch["targets"][..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        nll = lse - tgt
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(nll.shape, jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
        zl = cfg.z_loss * ((lse * lse) * mask).sum() / denom
    al = cfg.aux_loss_weight * aux
    loss = ce + zl + al
    metrics = {"loss": loss, "ce": ce, "z_loss": zl, "aux_loss": al,
               "tokens": denom}
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode state + single-token step (serving)
# ---------------------------------------------------------------------------

def _init_block_cache(cfg, block: Block, B: int, S_len: int) -> dict:
    dt = jnp.dtype(cfg.cache_dtype)
    if block.kind in ("attn", "moe", "shared_attn"):
        W = S_len if block.window is None else min(block.window, S_len)
        return {
            "k": jnp.zeros((B, W, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((B, W, cfg.n_kv_heads, cfg.head_dim), dt),
        }
    d = cfg.d_model
    if block.kind == "mamba2":
        din = cfg.ssm_expand * d
        nh = din // cfg.ssm_head_dim
        K1 = cfg.ssm_conv - 1
        return {
            "conv": {"x": jnp.zeros((B, K1, din), dt),
                     "B": jnp.zeros((B, K1, cfg.ssm_state), dt),
                     "C": jnp.zeros((B, K1, cfg.ssm_state), dt)},
            "ssm": jnp.zeros((B, nh, cfg.ssm_head_dim, cfg.ssm_state),
                             jnp.float32),
        }
    if block.kind == "mlstm":
        nh = cfg.n_heads
        hd = 2 * d // nh            # proj_factor=2 inner dim
        return {"C": jnp.zeros((B, nh, hd, hd), jnp.float32),
                "n": jnp.zeros((B, nh, hd), jnp.float32),
                "m": jnp.full((B, nh), -1e30, jnp.float32)}
    if block.kind == "slstm":
        z = jnp.zeros((B, d), jnp.float32)
        return {"c": z, "n": z, "h": z, "m": jnp.full((B, d), -1e30,
                                                      jnp.float32)}
    raise ValueError(block.kind)


def init_decode_state(cfg, B: int, S_len: int) -> dict:
    """Per-segment stacked caches mirroring params['segments']."""
    segs = []
    for block, n in segments(cfg):
        one = _init_block_cache(cfg, block, B, S_len)
        segs.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one))
    return {"segments": segs, "pos": jnp.zeros((), jnp.int32)}


def _decode_attn(p, cfg, block: Block, x, pool, layer, pos):
    """One-token windowed/full attention against a (possibly ring) cache.

    ``pool`` is the segment's stacked cache (``k``/``v`` of shape
    ``[n_layers, B, W, n_kv, hd]``) and ``layer`` the (traced) index of
    this layer in it.  The new K/V row of each slot is written into the
    pool in place and attention reads the layer's slab back out of it, so
    a decode step moves one row a slot per layer, not the whole pool.

    ``pos`` is a scalar (legacy fixed-batch decode: every sequence at the
    same position) or a ``[B]`` vector (continuous-batching pool: each slot
    at its own position).  The vector path writes the new K/V with a
    batched scatter instead of ``dynamic_update_slice`` — identical
    values, batched indices.
    """
    W = pool["k"].shape[2]
    B = x.shape[0]
    ring = block.window is not None and block.window <= W
    per_slot = jnp.ndim(pos) > 0
    slot = pos % W if ring else pos
    if per_slot:
        positions = pos[:, None].astype(jnp.int32)        # [B,1]
    else:
        positions = jnp.full((1,), pos, dtype=jnp.int32)
    q, k, v = L._qkv(p["attn"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                     positions)
    with jax.named_scope("kv_write"):
        if per_slot:
            # batched scatter, one row per slot (out-of-range slots — a
            # full cache that ran past its page — drop the write)
            rows = jnp.arange(B)

            def put(dst, new):
                return dst.at[layer, rows, slot].set(
                    new[:, 0].astype(dst.dtype), mode="drop")
        else:
            def put(dst, new):
                return lax.dynamic_update_slice(
                    dst, new[None].astype(dst.dtype), (layer, 0, slot, 0, 0))
        pool = {"k": put(pool["k"], k), "v": put(pool["v"], v)}
    ck = lax.dynamic_index_in_dim(pool["k"], layer, keepdims=False)
    cv = lax.dynamic_index_in_dim(pool["v"], layer, keepdims=False)
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = nh // nkv
    with jax.named_scope("decode_attn"):
        qg = q.reshape(B, 1, nkv, g, hd)
        s = jnp.einsum("btkgh,bskh->bkgs", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(hd)
        # cache slot s holds absolute position: s (no window) or
        # ring-decoded
        kpos = jnp.arange(W)[None, :] if per_slot else jnp.arange(W)
        posb = pos[:, None] if per_slot else pos
        slotb = slot[:, None] if per_slot else slot
        if ring:
            # ring slots hold positions pos-W+1..pos; valid if <= pos and
            # fresh
            age = (slotb - kpos) % W
            abs_pos = posb - age
            valid = ((abs_pos >= 0) & (abs_pos <= posb)
                     & (posb - abs_pos < block.window))
        else:
            valid = kpos <= posb
            if block.window is not None:
                valid &= (posb - kpos) < block.window
        vmask = (valid[:, None, None, :] if per_slot
                 else valid[None, None, None, :])
        s = jnp.where(vmask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskh->bkgh", w, cv.astype(jnp.float32))
    o = o.reshape(B, 1, nh * hd).astype(x.dtype)
    out = L.dense(o, p["attn"]["wo"])
    return out, pool


def _decode_block(p, cfg, block: Block, x, cache, pos, layer=None):
    """One layer's decode step.  For attention-family blocks ``cache`` is
    the segment's stacked K/V pool and ``layer`` this layer's index in it
    (the pool comes back with the layer's rows written); recurrent blocks
    take and return their own layer's state."""
    if block.kind in ("attn", "moe", "shared_attn"):
        h, cache = _decode_attn(p, cfg, block, x, cache, layer, pos)
        x = x + h
        y = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if block.kind == "moe":
            m, _ = M.moe(p["moe"], cfg, y)
        else:
            m = L.mlp(p["mlp"], cfg, y)
        return x + m, cache
    if block.kind == "mamba2":
        h, st = S.mamba2(p["mamba"], cfg,
                         L.rmsnorm(x, p["ln1"], cfg.norm_eps), state=cache)
        x = x + h
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], cfg, L.rmsnorm(x, p["ln2"], cfg.norm_eps))
        return x, st
    if block.kind == "mlstm":
        h, st = S.mlstm(p["mlstm"], cfg,
                        L.rmsnorm(x, p["ln1"], cfg.norm_eps), state=cache)
        return x + h, st
    if block.kind == "slstm":
        h, st = S.slstm(p["slstm"], cfg,
                        L.rmsnorm(x, p["ln1"], cfg.norm_eps), state=cache,
                        return_state=True)
        return x + h, st
    raise ValueError(block.kind)


def decode_step(params, cfg, state, tokens, active=None) -> Tuple[jax.Array, dict]:
    """tokens: [B,1] int32 (or [B,1,frontend_dim]).  One decode step.

    ``state["pos"]`` may be a scalar (legacy fixed batch) or a ``[B]``
    vector (continuous-batching slot pool; see ``serve.kvcache``).  With an
    ``active`` mask (``[B]`` in {0,1}) only active slots advance their
    position — retired slots stay frozen until ``insert`` recycles them.

    The K/V pool is updated in place: each attention segment's stacked
    ``[n_layers, B, W, n_kv, hd]`` caches ride the layer loop as its carry
    and every layer writes one row a slot into them, so with the pool
    donated the step neither copies the pool nor rewrites whole slabs.
    The layout is unchanged.  Recurrent segments, whose states are O(1)
    in the sequence, scan them as per-layer ``xs``.

    Returns (logits [B,1,V], new_state)."""
    pos = state["pos"]
    x = _embed_in(params, cfg, tokens)
    new_segs = []
    for (block, n), seg_p, seg_c in zip(
            segments(cfg), params["segments"], state["segments"]):
        if block.kind == "shared_attn":
            x, seg_c = _decode_block(params["shared"], cfg, block, x, seg_c,
                                     pos, layer=0)
        elif block.kind in ("attn", "moe"):
            def body(carry, lp_i):
                (h, pool), (lp, i) = carry, lp_i
                return _decode_block(lp, cfg, block, h, pool, pos, i), None

            (x, seg_c), _ = lax.scan(
                body, (x, seg_c), (seg_p, jnp.arange(n, dtype=jnp.int32)))
        else:
            def body(h, pc):
                lp, lc = pc
                return _decode_block(lp, cfg, block, h, lc, pos)

            x, seg_c = lax.scan(body, x, (seg_p, seg_c))
        new_segs.append(seg_c)
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("btd,dv->btv", x, head)
    adv = 1 if active is None else active.astype(jnp.int32)
    return logits, {"segments": new_segs, "pos": pos + adv}


def prefill(params, cfg, inputs, length=None) -> Tuple[jax.Array, dict]:
    """Full-sequence forward that also fills a decode state.

    For KV layers the cache is the (windowed) K/V run; recurrent layers
    carry their final states.  Returns (last-token logits [B,1,V], state).

    ``length`` (traced int32 scalar, optional) marks the number of real
    tokens when ``inputs`` is right-padded to a fixed shape (the
    continuous-batching insert path: one compile covers every prompt
    length).  Causality keeps positions ``< length`` unaffected by the
    padding; the returned logits are taken at position ``length - 1``, the
    decode position starts at ``length``, and windowed ring caches are laid
    out from the real tail so slot ``q % W`` holds position ``q`` — exactly
    the convention ``decode_step`` expects.  Padded K/V beyond ``length``
    stays in full caches but is masked by ``kpos <= pos`` until decode
    overwrites it in place.  Only attention-family blocks support
    ``length``: a recurrent state would integrate the pad tokens.
    """
    B, T = inputs.shape[:2]
    positions = jnp.arange(T, dtype=jnp.int32)
    if length is not None:
        bad = [b.kind for b, _ in segments(cfg)
               if b.kind not in ("attn", "shared_attn")]
        if bad:
            raise NotImplementedError(
                f"padded prefill (length=...) unsupported for blocks "
                f"{sorted(set(bad))}: recurrent state would integrate the "
                f"padding, and MoE capacity dispatch lets pad tokens evict "
                f"real ones")
    x = _embed_in(params, cfg, inputs)
    segs = []
    for (block, n), seg_p in zip(segments(cfg), params["segments"]):
        if block.kind == "shared_attn":
            x, c = _prefill_block(params["shared"], cfg, block, x, positions,
                                  length)
            segs.append(jax.tree.map(lambda a: a[None], c))
            continue

        def body(h, lp):
            h, c = _prefill_block(lp, cfg, block, h, positions, length)
            return h, c

        x, cs = lax.scan(body, x, seg_p)
        segs.append(cs)
    if length is None:
        xl, pos_out = x[:, -1:], jnp.asarray(T, jnp.int32)
    else:
        xl = lax.dynamic_slice_in_dim(x, jnp.maximum(length - 1, 0), 1, axis=1)
        pos_out = jnp.asarray(length, jnp.int32)
    with jax.named_scope("head"):
        x = L.rmsnorm(xl, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("btd,dv->btv", x, head)
    return logits, {"segments": segs, "pos": pos_out}


def _prefill_block(p, cfg, block: Block, x, positions, length=None):
    """Forward one block over the full sequence, returning its decode cache."""
    if block.kind in ("attn", "moe", "shared_attn"):
        T = x.shape[1]
        y = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(p["attn"], cfg, y, positions)
        h = L.attention(p["attn"], cfg, y, positions, window=block.window)
        x = x + h
        z = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if block.kind == "moe":
            m, _ = M.moe(p["moe"], cfg, z)
        else:
            m = L.mlp(p["mlp"], cfg, z)
        x = x + m
        dt = jnp.dtype(cfg.cache_dtype)
        if block.window is not None and block.window < T:
            W = block.window
            if length is None:
                # ring layout: slot t holds position (T - W + t') where the
                # ring index matches decode's pos % W convention
                tail_k, tail_v = k[:, T - W:], v[:, T - W:]
                roll = (T - W) % W
                ck = jnp.roll(tail_k, shift=roll, axis=1).astype(dt)
                cv = jnp.roll(tail_v, shift=roll, axis=1).astype(dt)
            else:
                # dynamic-length ring: slot s holds the newest real position
                # congruent to s mod W, i.e. q(s) = (L-1) - ((L-1-s) mod W);
                # slots with q(s) < 0 (short prompts) stay zero and are
                # masked by decode's freshness check until overwritten.
                s_idx = jnp.arange(W)
                last = length - 1
                q_idx = last - ((last - s_idx) % W)
                ok = (q_idx >= 0)[None, :, None, None]
                qc = jnp.clip(q_idx, 0, T - 1)
                ck = jnp.where(ok, jnp.take(k, qc, axis=1), 0).astype(dt)
                cv = jnp.where(ok, jnp.take(v, qc, axis=1), 0).astype(dt)
        else:
            ck, cv = k.astype(dt), v.astype(dt)
        return x, {"k": ck, "v": cv}
    if block.kind == "mamba2":
        h, st = S.mamba2(p["mamba"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                         return_state=True)
        x = x + h
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], cfg, L.rmsnorm(x, p["ln2"], cfg.norm_eps))
        st["conv"] = jax.tree.map(
            lambda a: a.astype(jnp.dtype(cfg.cache_dtype)), st["conv"])
        return x, st
    if block.kind == "mlstm":
        h, st = S.mlstm(p["mlstm"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                        return_state=True)
        return x + h, st
    if block.kind == "slstm":
        h, st = S.slstm(p["slstm"], cfg, L.rmsnorm(x, p["ln1"], cfg.norm_eps),
                        return_state=True)
        return x + h, st
    raise ValueError(block.kind)


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
