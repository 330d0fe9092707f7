"""SPMD entry points of the ``pallas_fused`` collective backend.

Same calling convention as ``collectives.shmap`` (call inside shard_map;
``axis`` may be a name or a tuple of names), same schedules (the static
tables from ``core.tables``), same wire traffic (one ``lax.ppermute`` per
schedule step) — but every step's *local* work runs as one fused Pallas
kernel from ``kernel.py`` instead of a slice/add/concat HLO chain:

  * butterfly RS: the keep-slice, the reduction, and the next step's
    send-half pack collapse into ``rs_step_kernel`` (the first step's pack
    is a bare slice — there is no earlier kernel to fuse it into);
  * butterfly AG: the concat/concat/select triple collapses into
    ``ag_step_kernel``;
  * ring RS/AG: the read-modify-write of the rotating block runs in place
    through ``ring_update_kernel`` (the send-slice stays a plain slice —
    it is a pure copy XLA folds into the ppermute);
  * ``matmul_reduce_scatter`` / ``allgather_matmul``: the TP contraction
    absorbs the Sec. 4.3.1 block permutation of its adjacent schedule
    step (output writes resp. LHS reads go through the permuted block
    index map), overlapping the matmul with the first/last exchange.

Arithmetic order matches shmap exactly (``kept + recv``), so the fp32
results are bit-for-bit identical to the shmap backend.  The kernels
need every rank block to be whole tiles (:func:`block_align`); a payload
whose rank block is not runs the shmap schedule instead
(:func:`shmap_fallback`) — the same bits and the same wire bytes, where
zero-padding would move up to 8x (int8: 32x) the payload's bytes,
which neither the ``auto`` selector nor the link-byte counters price.
``interpret=None`` resolves through :func:`default_interpret` —
interpreted off-TPU (CPU tier-1), compiled on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.collectives import compression as comp
from repro.collectives import shmap
from repro.core import tables as tb

from . import kernel as K

Axis = shmap.Axis

_KIND = {"bine": "bine_dd", "recdoub": "recdoub_dd"}

#: schedule families the fused kernels execute
ALGOS = ("bine", "recdoub", "ring")


def default_interpret() -> bool:
    """How the kernels run when the caller does not say: compiled on TPU,
    interpreted elsewhere.  Interpreted kernels need a ``shard_map`` with
    ``check_vma=False``: Pallas' interpreter evaluates the scalar-prefetch
    block index maps without the values' varying-axes types."""
    return jax.default_backend() != "tpu"


def _interp(interpret):
    return default_interpret() if interpret is None else interpret


def block_align(dtype, wire: str = "plain") -> int:
    """Per-rank block alignment (elements) the compiled step kernels
    need: whole tiles for plain payloads (1024 elements of a 32-bit
    dtype, 2048 of a 16-bit, 4096 of an 8-bit one); whole 32-row tiles
    of the int8 RS kernel's codec-chunk rows for the int8 wire."""
    if wire == "int8":
        return comp.WIRE_CHUNK * 32
    return K.CHUNK * max(1, 4 // jnp.dtype(dtype).itemsize)


def shmap_fallback(blk: int, dtype, wire: str = "plain") -> bool:
    """True when a rank block of ``blk`` elements is not whole kernel
    tiles, so the fused entry runs the (bit-identical) shmap schedule."""
    return blk % block_align(dtype, wire) != 0


# ---------------------------------------------------------------------------
# Butterfly cores
# ---------------------------------------------------------------------------

def _rs_core_fused(buf, axis: Axis, bt: tb.ButterflyTables, interpret):
    idx = shmap.axis_index(axis)
    half = buf.shape[0] // 2
    c = jnp.asarray(bt.cbit[0])[idx]
    send = lax.dynamic_slice(buf, ((1 - c) * half,), (half,))
    for i in range(bt.s):
        recv = lax.ppermute(send, axis, perm=list(bt.perms[i]))
        if i + 1 < bt.s:
            c_next = jnp.asarray(bt.cbit[i + 1])[idx]
            buf, send = K.rs_step_kernel(buf, recv, c, c_next,
                                         interpret=interpret)
            c = c_next
        else:
            buf = K.rs_step_kernel(buf, recv, c, interpret=interpret)
    return buf


def _ag_core_fused(buf, axis: Axis, bt: tb.ButterflyTables, interpret):
    idx = shmap.axis_index(axis)
    for i in range(bt.s - 1, -1, -1):
        recv = lax.ppermute(buf, axis, perm=list(bt.perms[i]))
        c = jnp.asarray(bt.cbit[i])[idx]
        buf = K.ag_step_kernel(buf, recv, c, interpret=interpret)
    return buf


# ---------------------------------------------------------------------------
# int8-wire butterfly cores (quantized payload, f32 accumulation in-kernel)
# ---------------------------------------------------------------------------

def _rs_core_fused_q(buf, axis: Axis, bt: tb.ButterflyTables, interpret):
    """int8 on the wire: each step ppermutes the (q, scales) pair the
    previous ``rs_step_kernel_q`` re-quantized; the kernel dequantizes the
    received half, accumulates in f32, and packs the next quantized send
    in the same HBM pass.  The first step's pack has no earlier kernel to
    ride, so it is a bare slice + ``quantize_wire``."""
    idx = shmap.axis_index(axis)
    half = buf.shape[0] // 2
    c = jnp.asarray(bt.cbit[0])[idx]
    send = lax.dynamic_slice(buf, ((1 - c) * half,), (half,))
    q, s = comp.quantize_wire(send)
    for i in range(bt.s):
        rq, rs = lax.ppermute((q, s), axis, perm=list(bt.perms[i]))
        if i + 1 < bt.s:
            c_next = jnp.asarray(bt.cbit[i + 1])[idx]
            buf, q, s = K.rs_step_kernel_q(buf, rq, rs, c, c_next,
                                           interpret=interpret)
            c = c_next
        else:
            buf = K.rs_step_kernel_q(buf, rq, rs, c, interpret=interpret)
    return buf


def _ag_core_fused_q(q, s, axis: Axis, bt: tb.ButterflyTables, interpret):
    """Moves an encoded (q, scales) pair through the butterfly: the int8
    payload merges through ``ag_step_kernel`` (dtype-agnostic placement
    pass); the scales — 1/WIRE_CHUNK of the payload — merge as plain
    concats."""
    idx = shmap.axis_index(axis)
    for i in range(bt.s - 1, -1, -1):
        rq = lax.ppermute(q, axis, perm=list(bt.perms[i]))
        rs = lax.ppermute(s, axis, perm=list(bt.perms[i]))
        c = jnp.asarray(bt.cbit[i])[idx]
        q = K.ag_step_kernel(q, rq, c, interpret=interpret)
        s = jnp.where(c == 0, jnp.concatenate([s, rs]),
                      jnp.concatenate([rs, s]))
    return q, s


def reduce_scatter_q(x, axis: Axis, algo: str = "bine", interpret=None):
    """int8-wire fused reduce-scatter: full vector -> this rank's reduced
    block (float32).  Bit-identical to ``shmap.reduce_scatter_q`` (same
    quantize points, same arithmetic); NOT bit-identical to the f32 path —
    per-element error is bounded by the received chunk's scale / 2.

    Rank blocks that are not whole kernel tiles run the shmap codec path
    (:func:`shmap_fallback`).
    """
    p = shmap.axis_size(axis)
    v = x.reshape(-1).astype(jnp.float32)
    if p == 1:
        return v.reshape(x.shape)
    if algo not in _KIND:
        raise ValueError(f"int8 wire supports bine/recdoub, not {algo!r}")
    assert v.shape[0] % p == 0, "reduce_scatter needs len divisible by p"
    if shmap_fallback(v.shape[0] // p, jnp.float32, "int8"):
        return shmap.reduce_scatter_q(v, axis, algo)
    bt = tb.butterfly_tables(_KIND[algo], p)
    return _rs_core_fused_q(shmap.permute_blocks(v, bt.inv_final), axis, bt,
                            _interp(interpret))


def allgather_q(x, axis: Axis, algo: str = "bine", interpret=None):
    """int8-wire fused allgather: this rank's block -> full vector
    (float32).  Quantize-once / move / dequantize-once, exactly as
    ``shmap.allgather_q`` — every rank decodes the same (q, scales)
    vector, own block included, so gathered params agree across ranks.
    Blocks that are not whole kernel tiles run the shmap codec path."""
    p = shmap.axis_size(axis)
    v = x.reshape(-1).astype(jnp.float32)
    if p == 1:
        return v
    if algo not in _KIND:
        raise ValueError(f"int8 wire supports bine/recdoub, not {algo!r}")
    if shmap_fallback(v.shape[0], jnp.float32, "int8"):
        return shmap.allgather_q(v, axis, algo)
    bt = tb.butterfly_tables(_KIND[algo], p)
    q, s = comp.quantize_wire(v)
    q, s = _ag_core_fused_q(q, s, axis, bt, _interp(interpret))
    return comp.dequantize_wire(shmap.permute_blocks(q, bt.final_block),
                                shmap.permute_blocks(s, bt.final_block))


# ---------------------------------------------------------------------------
# Collectives
# ---------------------------------------------------------------------------

def _rs_fused(v, axis: Axis, algo: str, interpret):
    """Flat vector, its p blocks already in the schedule's block order ->
    this rank's reduced block: the fused cores on whole-tile blocks, the
    shmap cores otherwise."""
    p = shmap.axis_size(axis)
    fused = not shmap_fallback(v.shape[0] // p, v.dtype)
    if algo == "ring":
        return (_ring_rs_flat(v, axis, interpret) if fused
                else shmap._ring_reduce_scatter(v, axis))
    bt = tb.butterfly_tables(_KIND[algo], p)
    return (_rs_core_fused(v, axis, bt, interpret) if fused
            else shmap._rs_core(v, axis, bt))


def _ag_fused(block, axis: Axis, algo: str, interpret):
    """This rank's block -> [p * blk] gathered, still in the schedule's
    block order (rank order for ring)."""
    fused = not shmap_fallback(block.shape[0], block.dtype)
    if algo == "ring":
        return (_ring_ag_flat(block, axis, interpret) if fused
                else shmap._ring_allgather(block, axis))
    bt = tb.butterfly_tables(_KIND[algo], shmap.axis_size(axis))
    return (_ag_core_fused(block, axis, bt, interpret) if fused
            else shmap._ag_core(block, axis, bt))


def _block_order(algo: str, p: int, inverse: bool):
    """Static block permutation between rank order and the schedule's
    block order (None for ring, which works in rank order)."""
    if algo == "ring":
        return None
    bt = tb.butterfly_tables(_KIND[algo], p)
    return bt.inv_final if inverse else bt.final_block


def reduce_scatter(x, axis: Axis, algo: str = "bine", interpret=None):
    """Full vector (len % p == 0) -> this rank's reduced block."""
    p = shmap.axis_size(axis)
    if p == 1:
        return x
    v = x.reshape(-1)
    assert v.shape[0] % p == 0, "reduce_scatter needs len divisible by p"
    perm = _block_order(algo, p, inverse=True)
    if perm is not None:
        v = shmap.permute_blocks(v, perm)
    return _rs_fused(v, axis, algo, _interp(interpret))


def allgather(x, axis: Axis, algo: str = "bine", interpret=None):
    """This rank's block -> full vector in rank order."""
    p = shmap.axis_size(axis)
    if p == 1:
        return x
    v = x.reshape(-1)
    full = _ag_fused(v, axis, algo, _interp(interpret))
    perm = _block_order(algo, p, inverse=False)
    return full if perm is None else shmap.permute_blocks(full, perm)


def allreduce(x, axis: Axis, algo: str = "bine", interpret=None):
    """Large-vector allreduce: fused RS (dist-doubling) + fused AG
    (dist-halving); no block permutation needed (the AG inverts the RS)."""
    p = shmap.axis_size(axis)
    if p == 1:
        return x
    interpret = _interp(interpret)
    v, n = shmap._pad_to(x.reshape(-1), p)
    full = _ag_fused(_rs_fused(v, axis, algo, interpret), axis, algo,
                     interpret)
    return full[:n].reshape(x.shape)


def reduce_scatter_dim(x, dim: int, axis: Axis, algo: str = "bine",
                       interpret=None):
    """Dim-general fused RS (the ZeRO gradient path): reduce over ``axis``
    ranks, scatter blocks of dim ``dim``.  Runs the flat fused core over a
    dim-fronted view (one transpose each way; the per-step slice/add
    chains are still fused away)."""
    p = shmap.axis_size(axis)
    if p == 1:
        return x
    assert x.shape[dim] % p == 0, (x.shape, dim, p)
    xm = jnp.moveaxis(x, dim, 0)
    flat = reduce_scatter(xm.reshape(-1), axis, algo, interpret)
    out_shape = (xm.shape[0] // p,) + xm.shape[1:]
    return jnp.moveaxis(flat.reshape(out_shape), 0, dim)


def allgather_dim(x, dim: int, axis: Axis, algo: str = "bine",
                  interpret=None):
    """Inverse of :func:`reduce_scatter_dim`: gather blocks along ``dim``."""
    p = shmap.axis_size(axis)
    if p == 1:
        return x
    xm = jnp.moveaxis(x, dim, 0)
    flat = allgather(xm.reshape(-1), axis, algo, interpret)
    out_shape = (xm.shape[0] * p,) + xm.shape[1:]
    return jnp.moveaxis(flat.reshape(out_shape), 0, dim)


# ---------------------------------------------------------------------------
# Ring (fused read-modify-write; same rotation as shmap's ring)
# ---------------------------------------------------------------------------

def _ring_rs_flat(v, axis: Axis, interpret):
    p = shmap.axis_size(axis)
    idx = shmap.axis_index(axis)
    assert v.shape[0] % p == 0
    blk = v.shape[0] // p
    perm = shmap._ring_perm(p)
    # step t sends block (idx-t-1) — which step t-1 just updated, so the
    # kernel's second output IS the next send and no per-step slice exists
    send = lax.dynamic_slice(v, (((idx - 1) % p) * blk,), (blk,))
    for t in range(p - 1):
        recv = lax.ppermute(send, axis, perm=perm)
        ridx = (idx - t - 2) % p
        if t + 1 < p - 1:
            v, send = K.ring_update_kernel(v, recv, ridx, accumulate=True,
                                           return_updated=True,
                                           interpret=interpret)
        else:
            v = K.ring_update_kernel(v, recv, ridx, accumulate=True,
                                     interpret=interpret)
    return lax.dynamic_slice(v, (idx * blk,), (blk,))


def _ring_ag_flat(block, axis: Axis, interpret):
    p = shmap.axis_size(axis)
    idx = shmap.axis_index(axis)
    blk = block.shape[0]
    v = jnp.zeros((p * blk,), block.dtype)
    v = lax.dynamic_update_slice(v, block, (idx * blk,))
    perm = shmap._ring_perm(p)
    # step t forwards what step t-1 delivered (send_{t} = recv_{t-1}), so
    # the rotating chunk never needs re-slicing from the buffer
    send = block.reshape(-1)
    for t in range(p - 1):
        recv = lax.ppermute(send, axis, perm=perm)
        ridx = (idx - t - 1) % p
        v = K.ring_update_kernel(v, recv, ridx, accumulate=False,
                                 interpret=interpret)
        send = recv
    return v


# ---------------------------------------------------------------------------
# Fused matmul + schedule-edge collectives (TP contraction overlap)
# ---------------------------------------------------------------------------

def matmul_reduce_scatter(x, w, axis: Axis, algo: str = "bine",
                          interpret=None):
    """``reduce_scatter(x @ w)`` over ``axis``, rows scattered: rank r gets
    rows ``[r*m/p, (r+1)*m/p)`` of the rank-summed product.

    The matmul's output writes go straight to the reduce-scatter's
    pre-permuted block layout (``matmul_pack_kernel``), so the contraction
    overlaps the first schedule step and the Sec. 4.3.1 permutation costs
    nothing.  ``m % p == 0`` required.
    """
    p = shmap.axis_size(axis)
    m, n = x.shape[0], w.shape[1]
    if p == 1:
        y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
        return y.astype(jnp.result_type(x, w))
    assert m % p == 0, (m, p)
    interpret = _interp(interpret)
    if algo == "ring":
        perm = jnp.arange(p, dtype=jnp.int32)  # ring scatters in rank order
    else:
        perm = _block_order(algo, p, inverse=True)
    y = K.matmul_pack_kernel(x, w, perm, interpret=interpret)
    out = _rs_fused(y.reshape(-1), axis, algo, interpret)
    return out.reshape(m // p, n)


def allgather_matmul(x, w, axis: Axis, algo: str = "bine", interpret=None):
    """``allgather(x over axis) @ w``: rank r contributes rows
    ``[r*mb, (r+1)*mb)`` of the gathered LHS; every rank returns the full
    ``[p*mb, n]`` product.

    The allgather's final block un-permute is folded into the matmul's LHS
    reads (``gather_matmul_kernel``), overlapping the contraction with the
    last schedule step.
    """
    p = shmap.axis_size(axis)
    if p == 1:
        y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
        return y.astype(jnp.result_type(x, w))
    interpret = _interp(interpret)
    mb, k = x.shape
    g = _ag_fused(x.reshape(-1), axis, algo, interpret)
    perm = _block_order(algo, p, inverse=False)
    if perm is None:
        perm = jnp.arange(p, dtype=jnp.int32)
    return K.gather_matmul_kernel(g.reshape(p * mb, k), w, perm,
                                  interpret=interpret)
