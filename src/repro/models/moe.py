"""Mixture-of-Experts: top-k router, capacity dispatch, two execution paths.

Weights are stored in *expert-block* layout: ``E·ep_blocks`` stacked units
of ``d_ff / ep_blocks`` columns each ([EB, d, ffb]), so the unit count
divides the model axis for every assigned MoE arch (mixtral: 8e x 2 blocks
= 16; phi3.5: 16e x 1 = 16) and the stack dim shards cleanly.

Paths:
  * ``_moe_dense`` — single-device / fallback: argsort capacity dispatch +
    batched expert einsum (NOT a one-hot einsum, so HLO FLOPs track active
    FLOPs and the roofline's MODEL/HLO ratio stays honest);
  * ``_moe_ep`` — expert parallelism under a NESTED manual shard_map over
    the model axis: tokens stay sequence-sharded, the router runs locally,
    and dispatch/combine are alltoalls.  The alltoall algorithm follows the
    paper's size switch (Sec. 4.4/5.1.2): the logarithmic Bine butterfly
    for small payloads (decode regime), XLA's linear alltoall for large
    ones (training) — exactly the regime split the paper measures.

Both paths compute identical math (tests/models/test_moe_ep.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P


from .layers import dense, init_dense

#: decision-table preset for the dispatch/combine alltoall (paper Sec.
#: 4.4/5.1.2: log algorithms win small payloads — the decode regime —
#: linear wins large ones).  The old fixed A2A_SMALL_BYTES threshold is
#: replaced by the topology-aware selector; override per deployment.
A2A_TOPOLOGY = "tpu_multipod"


def a2a_backend(n: int, buffer_bytes: int, topology: str = None) -> str:
    """Alltoall algorithm for the EP dispatch/combine.

    ``buffer_bytes`` is the full per-rank alltoall buffer (all n
    destination blocks — the decision table's full-vector convention).
    Consults the topology decision table (repro.topology).  The log
    butterflies read this chip's model-axis index from
    ``shmap.axis_index_hints`` (``_moe_ep`` passes it as data: Shardy
    rejects ``lax.axis_index`` inside a nested manual region).
    """
    from repro.topology import select_backend
    return select_backend("alltoall", n, buffer_bytes,
                          topology or A2A_TOPOLOGY)


def init_moe(key, cfg) -> dict:
    d, f, e, nb = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.ep_blocks
    eb, ffb = e * nb, f // nb
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    return {
        "router": init_dense(ks[0], d, e, dt),
        "wi": (jax.random.normal(ks[1], (eb, d, ffb), jnp.float32) * s_in).astype(dt),
        "wg": (jax.random.normal(ks[2], (eb, d, ffb), jnp.float32) * s_in).astype(dt),
        "wo": (jax.random.normal(ks[3], (eb, ffb, d), jnp.float32) * s_out).astype(dt),
    }


def _route(router_w, cfg, xt):
    """xt: [N, d] -> (gate_vals [N,K], gate_idx [N,K], aux scalar)."""
    E, K = cfg.n_experts, cfg.top_k
    logits = dense(xt, router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(onehot.mean(0) * probs.mean(0))
    return gate_vals, gate_idx, aux


def moe(p, cfg, x) -> Tuple[jax.Array, jax.Array]:
    """x: [B, T, d] -> (out [B, T, d], aux_loss scalar)."""
    from . import sharding as sh

    n = sh.model_parallel()
    B, T, d = x.shape
    EB = cfg.n_experts * cfg.ep_blocks
    if n > 1 and EB % n == 0 and T % n == 0:
        return _moe_ep(p, cfg, x, n)
    return _moe_dense(p, cfg, x)


# ---------------------------------------------------------------------------
# Dense (single-device oracle) path
# ---------------------------------------------------------------------------

def _moe_dense(p, cfg, x) -> Tuple[jax.Array, jax.Array]:
    B, T, d = x.shape
    E, K, nb = cfg.n_experts, cfg.top_k, cfg.ep_blocks
    N = B * T
    xt = x.reshape(N, d)
    gate_vals, gate_idx, aux = _route(p["router"], cfg, xt)

    cap = max(int(math.ceil(N * K / E * cfg.capacity_factor)), 1)
    flat_e = gate_idx.reshape(-1)                             # [N*K]
    flat_t = jnp.repeat(jnp.arange(N), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, stok, sg = flat_e[order], flat_t[order], flat_g[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(N * K) - seg_start[se]
    keep = pos_in_e < cap
    slot = jnp.where(keep, se * cap + pos_in_e, E * cap)

    buf_tok = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(
        stok.astype(jnp.int32), mode="drop")
    buf_valid = jnp.zeros((E * cap + 1,), jnp.bool_).at[slot].set(
        keep, mode="drop")
    xe = xt[buf_tok[:E * cap]]
    xe = jnp.where(buf_valid[:E * cap, None], xe, 0).reshape(E, cap, d)

    # expert FFN over blocks: wi/wg are [E*nb, d, ffb]; wo [E*nb, ffb, d]
    xeb = jnp.repeat(xe, nb, axis=0)                          # [E*nb, cap, d]
    h = jnp.einsum("ecd,edf->ecf", xeb, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xeb, p["wg"])
    h = (jax.nn.silu(g) if cfg.act == "swiglu"
         else jax.nn.gelu(g, approximate=True)) * h
    yb = jnp.einsum("ecf,efd->ecd", h, p["wo"])               # block partials
    ye = yb.reshape(E, nb, cap, d).sum(axis=1).reshape(E * cap, d)

    out = jnp.zeros((N, d), ye.dtype)
    vals = ye[jnp.clip(slot, 0, E * cap - 1)]
    vals = vals * (sg * keep)[:, None].astype(ye.dtype)
    out = out.at[stok].add(vals)
    return out.reshape(B, T, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel path (nested manual shard_map over the model axis)
# ---------------------------------------------------------------------------

def _moe_ep(p, cfg, x, n: int) -> Tuple[jax.Array, jax.Array]:
    """Tokens sequence-sharded over the model axis; expert blocks sharded;
    dispatch/combine via alltoall (paper-size-switched algorithm)."""
    from repro.collectives import shmap as coll
    from .sharding import MODEL_AXIS

    B, T, d = x.shape
    E, K, nb = cfg.n_experts, cfg.top_k, cfg.ep_blocks
    EB = E * nb
    Lb = EB // n                    # expert blocks per chip
    NL = B * (T // n)               # local tokens per chip
    # capacity per (source chip, dest chip): balanced-expert expectation
    # x cf headroom; static so the alltoall payload is fixed-size
    cap = max(int(math.ceil(NL * K * nb / n * cfg.capacity_factor)), 4)
    # full per-rank dispatch buffer: n destinations x cap slots x d
    a2a = a2a_backend(n, n * cap * d * jnp.dtype(cfg.dtype).itemsize)

    def body(xl, router, wi, wg, wo, idx_arr):
        # xl: [B, T/n, d]; wi/wg: [Lb, d, ffb]; wo: [Lb, ffb, d]
        # idx_arr: [1] = this chip's model-axis index (passed as a sharded
        # arange: lax.axis_index inside a NESTED shard_map trips a Shardy
        # lowering bug — "axis already bound by parent manual computation")
        with coll.axis_index_hints({MODEL_AXIS: idx_arr[0]}):
            return _body(xl, router, wi, wg, wo, idx_arr)

    def _body(xl, router, wi, wg, wo, idx_arr):
        Nl = B * (T // n)
        xt = xl.reshape(Nl, d)
        gate_vals, gate_idx, aux = _route(router, cfg, xt)
        aux = lax.pmean(aux, MODEL_AXIS)

        # destination CHIP for each (token, k, block_of_expert)
        flat_e = gate_idx.reshape(-1)                         # [Nl*K]
        blocks = flat_e[:, None] * nb + jnp.arange(nb)[None]  # [Nl*K, nb]
        dest = (blocks // Lb).reshape(-1)                     # [Nl*K*nb]
        tok = jnp.repeat(jnp.arange(Nl), K * nb)
        gv = jnp.repeat(gate_vals.reshape(-1), nb)

        # capacity slotting per dest chip
        order = jnp.argsort(dest, stable=True)
        sd, stok, sg = dest[order], tok[order], gv[order]
        sblk = blocks.reshape(-1)[order]
        seg = jnp.searchsorted(sd, jnp.arange(n), side="left")
        pos = jnp.arange(sd.shape[0]) - seg[sd]
        keep = pos < cap
        slot = jnp.where(keep, sd * cap + pos, n * cap)

        send = jnp.zeros((n * cap + 1, d), xl.dtype)
        send = send.at[slot].set(jnp.where(keep[:, None], xt[stok], 0),
                                 mode="drop")
        send_blk = jnp.full((n * cap + 1,), -1, jnp.int32).at[slot].set(
            jnp.where(keep, sblk, -1).astype(jnp.int32), mode="drop")
        send = send[:n * cap].reshape(n, cap, d)
        send_blk = send_blk[:n * cap].reshape(n, cap)

        # ---- dispatch alltoall (selector-chosen algorithm) ----
        # a2a comes from the topology decision table: the log butterflies
        # for payloads/rank-counts where they are predicted faster, XLA's
        # linear alltoall otherwise.
        if a2a == "xla":
            recv = lax.all_to_all(send, MODEL_AXIS, 0, 0, tiled=False)
            recv_blk = lax.all_to_all(send_blk, MODEL_AXIS, 0, 0, tiled=False)
        else:
            algo = "bruck" if a2a in ("bruck", "ring") else a2a
            recv = coll.all_to_all(send, MODEL_AXIS, algo)
            recv_blk = coll.all_to_all(send_blk, MODEL_AXIS, algo)

        # ---- local expert blocks ----
        idx0 = idx_arr[0] * Lb
        xin = recv.reshape(n * cap, d)
        lb = recv_blk.reshape(n * cap) - idx0          # local block id or <0
        valid = (lb >= 0) & (lb < Lb)
        lb_c = jnp.clip(lb, 0, Lb - 1)
        # one matmul per local block, tokens masked per block (Lb is small)
        y = jnp.zeros((n * cap, d), jnp.float32)
        for b in range(Lb):
            m = (lb_c == b) & valid
            xb = jnp.where(m[:, None], xin, 0)
            h = jnp.einsum("cd,df->cf", xb, wi[b])
            g = jnp.einsum("cd,df->cf", xb, wg[b])
            h = (jax.nn.silu(g) if cfg.act == "swiglu"
                 else jax.nn.gelu(g, approximate=True)) * h
            y = y + jnp.einsum("cf,fd->cd", h, wo[b]).astype(jnp.float32)
        y = y.reshape(n, cap, d).astype(xl.dtype)

        # ---- combine alltoall (reverse) ----
        if a2a == "xla":
            back = lax.all_to_all(y, MODEL_AXIS, 0, 0, tiled=False)
        else:
            back = coll.all_to_all(y, MODEL_AXIS,
                                   "bruck" if a2a in ("bruck", "ring") else a2a)
        back = back.reshape(n * cap, d)

        # gather each (token,k,block) partial, weight, scatter-add
        part = back[jnp.clip(slot, 0, n * cap - 1)]
        part = part * (sg * keep)[:, None].astype(back.dtype)
        out = jnp.zeros((Nl, d), part.dtype).at[stok].add(part)
        return out.reshape(B, T // n, d), aux

    smapped = jax.shard_map(
        body,
        in_specs=(P(None, MODEL_AXIS, None), P(), P(MODEL_AXIS, None, None),
                  P(MODEL_AXIS, None, None), P(MODEL_AXIS, None, None),
                  P(MODEL_AXIS)),
        out_specs=(P(None, MODEL_AXIS, None), P()),
        axis_names={MODEL_AXIS}, check_vma=False)
    out, aux = smapped(x, p["router"], p["wi"], p["wg"], p["wo"],
                       jnp.arange(n, dtype=jnp.int32))
    return out.astype(x.dtype), aux
