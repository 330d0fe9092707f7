"""SPMD (shard_map) implementations of the eight Bine collectives.

Every paper schedule step becomes one ``lax.ppermute`` with a *static*
(src, dst) pair list; per-rank decisions (which half to keep, where an
incoming window lands) are table lookups on ``lax.axis_index``.  This is
the TPU-native translation of the paper's per-step MPI exchanges: XLA sees
a ``collective-permute`` chain it can schedule/overlap, and the dry-run
roofline counts its bytes directly from the HLO.

All functions MUST be called inside ``shard_map`` (they use axis names).
``axis`` may be a single name or a tuple of mesh axis names (flattened
row-major, e.g. ``("pod", "data")`` for the gradient/optimizer axis — the
pod-major order is what makes rank distance ≈ pod locality, the paper's
block-placement assumption).
"""

from __future__ import annotations

import math
from contextlib import contextmanager
from functools import lru_cache, partial
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.collectives import compression as comp
from repro.core import tables as tb
from repro.core.schedules import BLOCK_ALL, KIND_REDUCE, Schedule

Axis = Union[str, Tuple[str, ...]]


def axis_size(axis: Axis) -> int:
    if isinstance(axis, (tuple, list)):
        return int(np.prod([lax.axis_size(a) for a in axis]))
    return lax.axis_size(axis)


#: stack of {axis name -> traced index} pushed by ``axis_index_hints``
_INDEX_HINTS: list = []


@contextmanager
def axis_index_hints(hints):
    """Supply per-axis rank indices as *data* instead of ``lax.axis_index``.

    Shardy rejects ``lax.axis_index`` inside a nested manual region (the
    MoE expert-parallel ``shard_map`` inside the train step).  The caller
    passes each manual axis an ``arange`` sharded over that axis and
    registers the per-shard element here; every collective in this module
    then picks up the hint transparently.
    """
    _INDEX_HINTS.append(dict(hints))
    try:
        yield
    finally:
        _INDEX_HINTS.pop()


def axis_index(axis: Axis):
    if isinstance(axis, (tuple, list)):
        # row-major flatten, matching the tuple-axis convention of
        # axis_size and the schedule tables
        idx = axis_index(axis[0])
        for a in axis[1:]:
            idx = idx * lax.axis_size(a) + axis_index(a)
        return idx
    for hints in reversed(_INDEX_HINTS):
        if axis in hints:
            return hints[axis]
    return lax.axis_index(axis)


def _flatten(x):
    shape, dtype = x.shape, x.dtype
    return x.reshape(-1), (shape, dtype)


def permute_blocks(x, order, dim: int = 0):
    """Split ``x`` along ``dim`` into ``len(order)`` equal blocks and
    concatenate them in the static ``order``.

    Static slices, not a gather: on TPU a gather with constant row
    indices compiles in time that grows with the payload (25 s for a
    614 M-element gradient bucket), while slices stay flat."""
    blk = x.shape[dim] // len(order)
    return jnp.concatenate(
        [lax.slice_in_dim(x, int(b) * blk, (int(b) + 1) * blk, axis=dim)
         for b in order], axis=dim)


def _pad_to(v, mult: int):
    n = v.shape[0]
    pad = (-n) % mult
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return v, n


# ---------------------------------------------------------------------------
# Butterfly cores (vector halving / doubling) — paper Sec. 4.3
# ---------------------------------------------------------------------------

def _rs_core(buf, axis: Axis, bt: tb.ButterflyTables):
    """Vector-halving reduce-scatter over the butterfly; buf len % p == 0.

    Step i: send the (1-c)-half to the partner, keep the c-half, add.
    Largest messages travel the shortest modulo distance (distance-doubling),
    the paper's global-traffic lever.
    """
    idx = axis_index(axis)
    for i in range(bt.s):
        half = buf.shape[0] // 2
        c = jnp.asarray(bt.cbit[i])[idx]
        send = lax.dynamic_slice(buf, ((1 - c) * half,), (half,))
        kept = lax.dynamic_slice(buf, (c * half,), (half,))
        recv = lax.ppermute(send, axis, perm=list(bt.perms[i]))
        buf = kept + recv
    return buf


def _ag_core(buf, axis: Axis, bt: tb.ButterflyTables):
    """Vector-doubling allgather: the RS reversed (distance-halving —
    largest messages again at the shortest distance)."""
    idx = axis_index(axis)
    for i in range(bt.s - 1, -1, -1):
        recv = lax.ppermute(buf, axis, perm=list(bt.perms[i]))
        c = jnp.asarray(bt.cbit[i])[idx]
        lo_first = jnp.concatenate([buf, recv])
        hi_first = jnp.concatenate([recv, buf])
        buf = jnp.where(c == 0, lo_first, hi_first)
    return buf


_KIND = {"bine": "bine_dd", "recdoub": "recdoub_dd"}


def allreduce_butterfly(x, axis: Axis, algo: str = "bine"):
    """Large-vector allreduce: RS (dist-doubling) + AG (dist-halving).

    No data permutation is needed: the AG inverts the RS's block movement
    (paper Sec. 4.3.1, last option)."""
    p = axis_size(axis)
    if p == 1:
        return x
    bt = tb.butterfly_tables(_KIND[algo], p)
    v = x.reshape(-1)
    v, n = _pad_to(v, p)
    v = _rs_core(v, axis, bt)
    v = _ag_core(v, axis, bt)
    return v[:n].reshape(x.shape)


def allreduce_small(x, axis: Axis, algo: str = "bine"):
    """Small-vector allreduce: recursive doubling on the distance-halving
    butterfly — full vector each step, log2(p) α-latencies (paper Sec. 4.4)."""
    p = axis_size(axis)
    if p == 1:
        return x
    kind = {"bine": "bine_dh", "recdoub": "recdoub_dh"}[algo]
    perms = tb.small_butterfly_perms(kind, p)
    v = x
    for i in range(len(perms)):
        v = v + lax.ppermute(v, axis, perm=list(perms[i]))
    return v


def reduce_scatter(x, axis: Axis, algo: str = "bine"):
    """x: full vector (len % p == 0) -> this rank's reduced block.

    Pre-permutes blocks by the inverse contiguity layout (Sec. 4.3.1:
    block j -> position reverse(v(j))) so every transmission is contiguous
    and rank r ends with block r."""
    p = axis_size(axis)
    if p == 1:
        return x
    if algo == "ring":
        return _ring_reduce_scatter(x, axis)
    bt = tb.butterfly_tables(_KIND[algo], p)
    v = x.reshape(-1)
    assert v.shape[0] % p == 0, "reduce_scatter needs len divisible by p"
    return _rs_core(permute_blocks(v, bt.inv_final), axis, bt)


def allgather(x, axis: Axis, algo: str = "bine"):
    """x: this rank's block -> full vector (block-major, rank order)."""
    p = axis_size(axis)
    if p == 1:
        return x
    if algo == "ring":
        return _ring_allgather(x, axis)
    bt = tb.butterfly_tables(_KIND[algo], p)
    return permute_blocks(_ag_core(x.reshape(-1), axis, bt), bt.final_block)


# ---------------------------------------------------------------------------
# int8-wire butterfly RS / AG (quantized payload, f32 accumulation)
# ---------------------------------------------------------------------------
# Same schedules and the same ``kept + recv`` operand order as the f32
# cores, but the bytes that travel are int8 + per-chunk f32 scales
# (``compression.quantize_wire``'s shared chunk rule).  RS re-quantizes the
# freshly accumulated half before each send; AG quantizes once at entry,
# moves (q, scales) pairs through the whole butterfly, and decodes once at
# the end — so every rank (the block owner included) uses the decoded
# values and params stay consistent across ranks.

def _rs_core_q(buf, axis: Axis, bt: tb.ButterflyTables):
    """int8-wire vector-halving RS.  ``buf`` float32, len % p == 0.

    Step i: quantize the (1-c)-half at ``wire_chunk(half)``, ship
    (q, scales), dequantize the partner's half and accumulate in f32.
    Only what travels is quantized — the kept half stays full precision.
    """
    idx = axis_index(axis)
    for i in range(bt.s):
        half = buf.shape[0] // 2
        c = jnp.asarray(bt.cbit[i])[idx]
        send = lax.dynamic_slice(buf, ((1 - c) * half,), (half,))
        kept = lax.dynamic_slice(buf, (c * half,), (half,))
        q, s = comp.quantize_wire(send)
        rq = lax.ppermute(q, axis, perm=list(bt.perms[i]))
        rs = lax.ppermute(s, axis, perm=list(bt.perms[i]))
        buf = kept + comp.dequantize_wire(rq, rs)
    return buf


def _ag_core_q(q, s, axis: Axis, bt: tb.ButterflyTables):
    """int8-wire vector-doubling AG on an encoded (q, scales) pair.

    The c-ordered merges apply to q and scales separately; their windows
    double together because the codec chunk divides the block.
    """
    idx = axis_index(axis)
    for i in range(bt.s - 1, -1, -1):
        rq = lax.ppermute(q, axis, perm=list(bt.perms[i]))
        rs = lax.ppermute(s, axis, perm=list(bt.perms[i]))
        c = jnp.asarray(bt.cbit[i])[idx]
        q = jnp.where(c == 0, jnp.concatenate([q, rq]),
                      jnp.concatenate([rq, q]))
        s = jnp.where(c == 0, jnp.concatenate([s, rs]),
                      jnp.concatenate([rs, s]))
    return q, s


def reduce_scatter_q(x, axis: Axis, algo: str = "bine"):
    """int8-wire reduce-scatter: full vector -> this rank's reduced block
    (float32).  NOT bit-identical to the f32 path — each received half
    carries per-element error bounded by its chunk scale / 2 — but
    bit-identical to the ``pallas_fused`` int8 path, which quantizes at
    the same points with the same arithmetic."""
    p = axis_size(axis)
    v = x.reshape(-1).astype(jnp.float32)
    if p == 1:
        return v.reshape(x.shape)
    if algo not in _KIND:
        raise ValueError(f"int8 wire supports bine/recdoub, not {algo!r}")
    bt = tb.butterfly_tables(_KIND[algo], p)
    assert v.shape[0] % p == 0, "reduce_scatter needs len divisible by p"
    return _rs_core_q(permute_blocks(v, bt.inv_final), axis, bt)


def allgather_q(x, axis: Axis, algo: str = "bine"):
    """int8-wire allgather: this rank's block -> full vector (float32).

    Quantize-once / move / dequantize-once: the block is encoded at entry,
    the butterfly moves (q, scales), and the final un-permuted vector is
    decoded in one pass — own block included, so all ranks hold identical
    values with a single quantization error."""
    p = axis_size(axis)
    v = x.reshape(-1).astype(jnp.float32)
    if p == 1:
        return v
    if algo not in _KIND:
        raise ValueError(f"int8 wire supports bine/recdoub, not {algo!r}")
    bt = tb.butterfly_tables(_KIND[algo], p)
    q, s = comp.quantize_wire(v)
    q, s = _ag_core_q(q, s, axis, bt)
    return comp.dequantize_wire(permute_blocks(q, bt.final_block),
                                permute_blocks(s, bt.final_block))


# ---------------------------------------------------------------------------
# Dimension-general butterfly RS / AG (ZeRO-1 gradient/param sharding)
# ---------------------------------------------------------------------------
# Same schedules as the flat cores, but slicing along an arbitrary dim so a
# leaf keeps its other dims (and their auto-axis/model sharding) intact.

def _rs_core_dim(buf, dim: int, axis: Axis, bt: tb.ButterflyTables):
    idx = axis_index(axis)
    for i in range(bt.s):
        half = buf.shape[dim] // 2
        c = jnp.asarray(bt.cbit[i])[idx]
        send = lax.dynamic_slice_in_dim(buf, (1 - c) * half, half, axis=dim)
        kept = lax.dynamic_slice_in_dim(buf, c * half, half, axis=dim)
        recv = lax.ppermute(send, axis, perm=list(bt.perms[i]))
        buf = kept + recv
    return buf


def _ag_core_dim(buf, dim: int, axis: Axis, bt: tb.ButterflyTables):
    idx = axis_index(axis)
    for i in range(bt.s - 1, -1, -1):
        recv = lax.ppermute(buf, axis, perm=list(bt.perms[i]))
        c = jnp.asarray(bt.cbit[i])[idx]
        lo_first = jnp.concatenate([buf, recv], axis=dim)
        hi_first = jnp.concatenate([recv, buf], axis=dim)
        buf = jnp.where(c == 0, lo_first, hi_first)
    return buf


def reduce_scatter_dim(x, dim: int, axis: Axis, algo: str = "bine"):
    """Reduce over ``axis`` ranks; scatter blocks of dim ``dim``.

    x.shape[dim] must be divisible by the axis size p.  Rank r receives
    block r (contiguous; the Sec. 4.3.1 permutation is applied up front).
    """
    p = axis_size(axis)
    if p == 1:
        return x
    if algo == "ring":
        return _ring_rs_dim(x, dim, axis)
    bt = tb.butterfly_tables(_KIND[algo], p)
    assert x.shape[dim] % p == 0, (x.shape, dim, p)
    # pre-permute blocks along dim by inv_final so rank r ends with block r
    return _rs_core_dim(permute_blocks(x, bt.inv_final, dim), dim, axis, bt)


def allgather_dim(x, dim: int, axis: Axis, algo: str = "bine"):
    """Inverse of reduce_scatter_dim: gather blocks along dim in rank order."""
    p = axis_size(axis)
    if p == 1:
        return x
    if algo == "ring":
        return _ring_ag_dim(x, dim, axis)
    bt = tb.butterfly_tables(_KIND[algo], p)
    return permute_blocks(_ag_core_dim(x, dim, axis, bt), bt.final_block,
                          dim)


def _ring_rs_dim(x, dim: int, axis: Axis):
    p = axis_size(axis)
    idx = axis_index(axis)
    assert x.shape[dim] % p == 0
    blk = x.shape[dim] // p
    perm = _ring_perm(p)
    for t in range(p - 1):
        sidx = (idx - t - 1) % p
        chunk = lax.dynamic_slice_in_dim(x, sidx * blk, blk, axis=dim)
        recv = lax.ppermute(chunk, axis, perm=perm)
        ridx = (idx - t - 2) % p
        cur = lax.dynamic_slice_in_dim(x, ridx * blk, blk, axis=dim)
        x = lax.dynamic_update_slice_in_dim(x, cur + recv, ridx * blk, axis=dim)
    return lax.dynamic_slice_in_dim(x, idx * blk, blk, axis=dim)


def _ring_ag_dim(x, dim: int, axis: Axis):
    p = axis_size(axis)
    idx = axis_index(axis)
    blk = x.shape[dim]
    shape = list(x.shape)
    shape[dim] = p * blk
    v = jnp.zeros(shape, x.dtype)
    v = lax.dynamic_update_slice_in_dim(v, x, idx * blk, axis=dim)
    perm = _ring_perm(p)
    for t in range(p - 1):
        sidx = (idx - t) % p
        chunk = lax.dynamic_slice_in_dim(v, sidx * blk, blk, axis=dim)
        recv = lax.ppermute(chunk, axis, perm=perm)
        ridx = (idx - t - 1) % p
        v = lax.dynamic_update_slice_in_dim(v, recv, ridx * blk, axis=dim)
    return v


# ---------------------------------------------------------------------------
# Ring baselines
# ---------------------------------------------------------------------------

def _ring_perm(p: int):
    return [(r, (r + 1) % p) for r in range(p)]


def _ring_reduce_scatter(x, axis: Axis):
    p = axis_size(axis)
    idx = axis_index(axis)
    v = x.reshape(-1)
    assert v.shape[0] % p == 0
    blk = v.shape[0] // p
    perm = _ring_perm(p)
    for t in range(p - 1):
        sidx = (idx - t - 1) % p
        chunk = lax.dynamic_slice(v, (sidx * blk,), (blk,))
        recv = lax.ppermute(chunk, axis, perm=perm)
        ridx = (idx - t - 2) % p
        cur = lax.dynamic_slice(v, (ridx * blk,), (blk,))
        v = lax.dynamic_update_slice(v, cur + recv, (ridx * blk,))
    out = lax.dynamic_slice(v, (idx * blk,), (blk,))
    return out


def _ring_allgather(x, axis: Axis):
    p = axis_size(axis)
    idx = axis_index(axis)
    blk = x.reshape(-1).shape[0]
    v = jnp.zeros((p * blk,), x.dtype)
    v = lax.dynamic_update_slice(v, x.reshape(-1), (idx * blk,))
    perm = _ring_perm(p)
    for t in range(p - 1):
        sidx = (idx - t) % p
        chunk = lax.dynamic_slice(v, (sidx * blk,), (blk,))
        recv = lax.ppermute(chunk, axis, perm=perm)
        ridx = (idx - t - 1) % p
        v = lax.dynamic_update_slice(v, recv, (ridx * blk,))
    return v


def allreduce_ring(x, axis: Axis):
    p = axis_size(axis)
    if p == 1:
        return x
    v = x.reshape(-1)
    v, n = _pad_to(v, p)
    block = _ring_reduce_scatter(v, axis)
    full = _ring_allgather(block, axis)
    return full[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# Trees: broadcast / reduce (small vectors) — paper Sec. 4.5
# ---------------------------------------------------------------------------

_TREE = {"bine": "bine_dh", "binomial": "binomial_dh", "binomial_dd": "binomial_dd"}


def broadcast(x, axis: Axis, root: int = 0, algo: str = "bine"):
    p = axis_size(axis)
    if p == 1:
        return x
    tt = tb.tree_tables(_TREE[algo], p, root)
    idx = axis_index(axis)
    recv_step = jnp.asarray(tt.recv_step)[idx]
    buf = x
    for i in range(tt.s):
        recv = lax.ppermute(buf, axis, perm=list(tt.perms[i]))
        buf = jnp.where(recv_step == i, recv, buf)
    return buf


def reduce(x, axis: Axis, root: int = 0, algo: str = "bine"):
    """Tree reduce: reversed broadcast; each rank forwards its accumulator
    to its parent exactly once."""
    p = axis_size(axis)
    if p == 1:
        return x
    tt = tb.tree_tables(_TREE[algo], p, root)
    idx = axis_index(axis)
    s = tt.s
    acc = x
    for i in range(s):
        # reduce step i = reversed bcast step s-1-i, edges child -> parent
        pairs = [(dst, src) for (src, dst) in tt.perms[s - 1 - i]]
        contrib = lax.ppermute(acc, axis, perm=pairs)
        receives = jnp.asarray(
            np.array([any(d == r for _, d in pairs) for r in range(p)]))[idx]
        acc = acc + jnp.where(receives, contrib, jnp.zeros_like(contrib))
    return acc


# ---------------------------------------------------------------------------
# Gather / Scatter (paper Sec. 4.1 / 4.2)
# ---------------------------------------------------------------------------

def gather(x, axis: Axis, root: int = 0, algo: str = "bine"):
    """x: per-rank block -> full vector (valid at root; rank order)."""
    p = axis_size(axis)
    if p == 1:
        return x
    gt = tb.gather_tables({"bine": "bine_dh", "binomial": "binomial_dh"}[algo],
                          p, root)
    idx = axis_index(axis)
    v = x.reshape(-1)
    blk = v.shape[0]
    buf = jnp.zeros((p * blk,), v.dtype)
    own = jnp.asarray(gt.own_local)[idx] * blk
    buf = lax.dynamic_update_slice(buf, v, (own,))
    for j in range(gt.s):
        sz = gt.sizes[j] * blk
        chunk = lax.dynamic_slice(buf, (0,), (sz,))  # sender window starts at 0
        recv = lax.ppermute(chunk, axis, perm=list(gt.perms[j]))
        off = jnp.asarray(gt.recv_off[j])[idx] * blk
        cur = lax.dynamic_slice(buf, (off,), (sz,))
        is_r = jnp.asarray(gt.recv_mask[j])[idx]
        buf = lax.dynamic_update_slice(
            buf, jnp.where(is_r, recv, cur), (off,))
    return permute_blocks(buf, gt.root_unrot)


def scatter(x, axis: Axis, root: int = 0, algo: str = "bine"):
    """x: full vector (significant at root) -> this rank's block.

    ``bine`` uses the distance-doubling tree with the Sec. 4.3.1 position
    permutation (root-local, static) so all sends stay contiguous."""
    p = axis_size(axis)
    if p == 1:
        return x
    st = tb.scatter_tables(
        {"bine": "bine_dh", "bine_dd": "bine_dd",
         "binomial": "binomial_dh"}[algo], p, root)
    idx = axis_index(axis)
    v = x.reshape(-1)
    assert v.shape[0] % p == 0
    blk = v.shape[0] // p
    buf = permute_blocks(v, st.root_rot)
    for j in range(st.s):
        sz = st.sizes[j] * blk
        soff = jnp.asarray(st.send_off[j])[idx] * blk
        chunk = lax.dynamic_slice(buf, (soff,), (sz,))
        recv = lax.ppermute(chunk, axis, perm=list(st.perms[j]))
        is_r = jnp.asarray(st.recv_mask[j])[idx]
        cur = lax.dynamic_slice(buf, (0,), (sz,))
        buf = lax.dynamic_update_slice(buf, jnp.where(is_r, recv, cur), (0,))
    own = jnp.asarray(st.own_local)[idx] * blk
    return lax.dynamic_slice(buf, (own,), (blk,))


# ---------------------------------------------------------------------------
# Alltoall (paper Sec. 4.4)
# ---------------------------------------------------------------------------

def all_to_all(x, axis: Axis, algo: str = "bine"):
    """x: [p, ...] (row d destined to rank d) -> [p, ...] (row o from rank o).

    Logarithmic butterfly routing: n/2 bytes per step over log2(p) steps —
    the small-vector/large-p regime where Bruck-style algorithms win."""
    p = axis_size(axis)
    if p == 1:
        return x
    at = tb.alltoall_tables({"bine": "bine_dd", "bruck": "bruck",
                             "recdoub": "recdoub_dd"}[algo], p)
    idx = axis_index(axis)
    assert x.shape[0] == p, "all_to_all expects leading dim == axis size"
    buf = x.reshape(p, -1)
    for j in range(at.s):
        sidx = jnp.asarray(at.send_slots[j])[idx]
        chunk = buf[sidx]
        recv = lax.ppermute(chunk, axis, perm=list(at.perms[j]))
        ridx = jnp.asarray(at.recv_slots[j])[idx]
        buf = buf.at[ridx].set(recv)
    out = buf[jnp.asarray(at.final_slots)[idx]]
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# Schedule-IR executor: one ppermute per step, static block-id tables
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _schedule_tables(sched: Schedule):
    """Static per-step dispatch tables for ``run_schedule``.

    Requires full-permutation steps (every rank sends once and receives
    once, all messages the same block count) — true of every pow2
    butterfly/ring/composed schedule; adapter (non-pow2) schedules are
    not executable here.  Packing order is the message's ``blocks``
    tuple, so sender and receiver tables agree by construction.
    """
    p = sched.p
    out = []
    for step, kind in zip(sched.steps, sched.kinds):
        if len(step) != p:
            raise ValueError(
                f"run_schedule needs full-permutation steps; got "
                f"{len(step)} messages for p={p}")
        k = len(step[0].blocks)
        send = np.zeros((p, k), np.int32)
        recv = np.zeros((p, k), np.int32)
        perm = []
        for m in step:
            assert len(m.blocks) == k, "uneven block counts within a step"
            assert BLOCK_ALL not in m.blocks
            send[m.src] = m.blocks
            recv[m.dst] = m.blocks
            perm.append((int(m.src), int(m.dst)))
        out.append((kind, tuple(perm), send, recv))
    return tuple(out)


def run_schedule(v, axis: Axis, sched: Schedule):
    """Execute a block-schedule IR value on a ``[p, blk]`` buffer.

    Each step gathers the rank's send blocks (static table indexed by
    ``axis_index``), ships them in one ``lax.ppermute``, and lands them by
    kind: ``reduce`` accumulates (``.add``), ``copy``/``move`` install
    (``.set``).  Relinquished blocks simply go stale in the buffer — the
    IR's kind discipline (checked by the numpy oracle) guarantees they
    are never re-read, so the caller just slices what the collective
    defines as live at the end."""
    idx = axis_index(axis)
    for kind, perm, send, recv in _schedule_tables(sched):
        chunk = v[jnp.asarray(send)[idx]]
        got = lax.ppermute(chunk, axis, perm=list(perm))
        rids = jnp.asarray(recv)[idx]
        v = v.at[rids].add(got) if kind == KIND_REDUCE else v.at[rids].set(got)
    return v


def reduce_scatter_sched(x, axis: Axis, sched: Schedule):
    """Full vector -> own reduced block, via an RS schedule value (e.g.
    ``core.schedules.compose(\"reduce_scatter\", tiers)``)."""
    p = sched.p
    v = x.reshape(-1)
    assert v.shape[0] % p == 0, (v.shape, p)
    v = run_schedule(v.reshape(p, -1), axis, sched)
    return lax.dynamic_index_in_dim(v, axis_index(axis), axis=0,
                                    keepdims=False)


def allgather_sched(x, axis: Axis, sched: Schedule):
    """Own block -> full vector (rank order), via an AG schedule value."""
    p = sched.p
    blk = x.reshape(-1)
    v = jnp.zeros((p, blk.shape[0]), blk.dtype).at[axis_index(axis)].set(blk)
    return run_schedule(v, axis, sched).reshape(-1)


def allreduce_sched(x, axis: Axis, sched: Schedule):
    """Full-vector allreduce via a composed RS+AG schedule value."""
    p = sched.p
    v, n = _pad_to(x.reshape(-1), p)
    v = run_schedule(v.reshape(p, -1), axis, sched)
    return v.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# Hierarchical allreduce (paper Sec. 6.2) — intra-pod RS/AG + inter-pod AR
# ---------------------------------------------------------------------------

def allreduce_hier(x, axes: Sequence[Axis], algo: str = "bine"):
    """Arbitrary-depth hierarchy over mesh axes, innermost (fastest)
    first: RS down the stack — each level on a 1/p shard of the one
    above — allreduce at the top, AG back up.  The shard_map twin of
    ``core.schedules.compose`` over ``tiers = map(axis_size, axes)``;
    depth 2 is exactly ``allreduce_hierarchical``."""
    if len(axes) == 1:
        return allreduce_butterfly(x, axes[0], algo)
    inner = axes[0]
    p_in = axis_size(inner)
    if p_in == 1:
        return allreduce_hier(x, axes[1:], algo)
    v = x.reshape(-1)
    v, n = _pad_to(v, p_in)
    shard = reduce_scatter(v, inner, algo)
    shard = allreduce_hier(shard, axes[1:], algo)
    full = allgather(shard, inner, algo)
    return full[:n].reshape(x.shape)


def allreduce_hierarchical(x, inner_axis: Axis, outer_axis: Axis,
                           algo: str = "bine"):
    """RS within the (fast) inner axis, allreduce across the (slow) outer
    axis on the 1/p_in shard, AG within the inner axis.  Inter-group bytes
    drop from O(n) to n/p_in per rank — the NCCL-style hierarchy the paper
    evaluates on multi-GPU nodes, mapped to ICI(inner)/DCN(outer).  The
    depth-2 case of ``allreduce_hier``."""
    return allreduce_hier(x, (inner_axis, outer_axis), algo)
