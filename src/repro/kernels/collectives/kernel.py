"""Pallas kernels for the fused collective schedule steps.

Each butterfly/ring schedule step in ``collectives.shmap`` lowers to a
chain of separate HLO ops (dynamic-slice the kept half, dynamic-slice the
sent half, add, concat/select, dynamic-update-slice) that each round-trip
the vector through HBM.  The kernels here collapse one step's local work
into a single pass:

  * ``rs_step_kernel``   — incoming-chunk reduction (``kept + recv``)
    fused with the *next* step's outgoing-half pack: one read of the kept
    half (at its dynamic offset, via a scalar-prefetched block index map —
    the slice never materializes), one read of ``recv``, one write of the
    new window, and the next send-half peeled off in the same pass;
  * ``ag_step_kernel``   — the allgather merge (concat in c-order) as a
    single placement pass instead of concat/concat/select;
  * ``ring_update_kernel`` — the ring step's read-modify-write of one
    block, aliased in place (the rest of the buffer is never touched);
  * ``matmul_pack_kernel`` / ``gather_matmul_kernel`` — a tiled matmul
    whose output writes (resp. LHS reads) go through the reduce-scatter
    pre-permute (resp. allgather un-permute) block order, fusing the
    Sec. 4.3.1 contiguity permutation into the contraction.

All kernels are *local*: the inter-rank exchange stays a ``lax.ppermute``
issued by ``ops.py`` between kernel invocations, so XLA still schedules
and overlaps the wire traffic.  The work is chunked over the Pallas grid;
the TPU pipeline double-buffers the HBM->VMEM copies, so chunk ``i+1``
streams in while chunk ``i`` reduces.  Arithmetic order is identical to
the unfused shmap path (``kept + recv``), which is what makes the
``pallas_fused`` backend bit-for-bit with the shmap backend in fp32.

Validated against ``ref.py`` in interpret mode
(tests/kernels/test_fused_collectives.py), compiled for a v5e chip at
real bucket widths (tests/kernels/test_tpu_compile.py), following the
``kernels/flash_attention`` pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: default chunk cap (elements) for the 1-D step kernels; on the chip a
#: 1-D block must be whole 1024-element tiles, so compiled callers pass
#: payloads that are multiples of it (``ops`` runs other rank blocks on the
#: shmap path)
CHUNK = 1024

#: row cap of the int8 RS kernel's 2-D ``(rows, WIRE_CHUNK)`` blocks; on
#: the chip the int8 operand needs whole 32-row tiles
Q_ROWS = 128

#: lanes of the AG kernel's ``(rows, LANES)`` view of a flat payload, and
#: its row cap per block (512 rows: 256 KiB of f32)
LANES = 128
AG_ROWS = 512


def _pow2_divisor(n: int, cap: int = CHUNK) -> int:
    """Largest power of two <= cap dividing n (1 if n is odd)."""
    c = 1
    while c * 2 <= cap and n % (c * 2) == 0:
        c *= 2
    return c


def _out(shape, dtype, like):
    """Output ``ShapeDtypeStruct`` varying over the same manual axes as
    ``like``, so the kernels type-check under ``shard_map(check_vma=True)``."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# Reduce-scatter step: fused keep-slice + reduce (+ next-step send pack)
# ---------------------------------------------------------------------------

def _rs_step_body(cs_ref, buf_ref, recv_ref, out_ref, send_ref=None, *,
                  nsend=0):
    s = buf_ref[...] + recv_ref[...]
    out_ref[...] = s
    if send_ref is not None:
        w0 = (1 - cs_ref[1]) * nsend
        j = pl.program_id(0)

        @pl.when(jnp.logical_and(j >= w0, j < w0 + nsend))
        def _():
            send_ref[...] = s


def _send_map(nsend):
    """Block index of the outgoing half for grid step ``j``: the window's
    own block inside it, clamped to the window's edge outside it — so the
    block stays resident (no write-back) until the window has filled it."""
    def index(j, cs):
        return (jnp.clip(j - (1 - cs[1]) * nsend, 0, nsend - 1),)
    return index


def rs_step_kernel(buf, recv, c, c_next=None, *, interpret=False):
    """buf: [2h]; recv: [h] -> newbuf [h] (+ send [h//2] when c_next given).

    ``newbuf = buf[c*h : (c+1)*h] + recv``; the kept half is read directly
    at its dynamic offset through the scalar-prefetched block index map —
    no separate slice op ever materializes.  ``send`` is
    ``newbuf[(1-c_next)*q : +q]``, written block by block in the same pass.
    """
    h = recv.shape[0]
    assert buf.shape == (2 * h,), (buf.shape, h)
    if c_next is None:
        chunk = _pow2_divisor(h)
        cs = jnp.stack([jnp.asarray(c, jnp.int32)])
    else:
        assert h % 2 == 0, h
        chunk = _pow2_divisor(h // 2)
        cs = jnp.stack([jnp.asarray(c, jnp.int32),
                        jnp.asarray(c_next, jnp.int32)])
    nch = h // chunk
    block = pl.BlockSpec((chunk,), lambda j, cs: (j,))
    out_specs = [block]
    out_shape = [_out((h,), buf.dtype, buf)]
    if c_next is not None:
        out_specs.append(pl.BlockSpec((chunk,), _send_map(nch // 2)))
        out_shape.append(_out((h // 2,), buf.dtype, buf))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nch,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda j, cs: (cs[0] * nch + j,)),
            block,
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        functools.partial(_rs_step_body, nsend=nch // 2),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )(cs, buf, recv)
    return out[0] if c_next is None else tuple(out)


# ---------------------------------------------------------------------------
# int8-wire reduce-scatter step: dequantize + reduce + re-quantize, one pass
# ---------------------------------------------------------------------------

def _rs_step_q_body(cs_ref, buf_ref, rq_ref, rs_ref, out_ref, sq_ref=None,
                    ss_ref=None, *, nsend=0):
    # rows are codec chunks: [R, ch] values, [R, 1] pow2 scales
    v = buf_ref[...] + rq_ref[...].astype(jnp.float32) * rs_ref[...]
    out_ref[...] = v
    if sq_ref is not None:
        w0 = (1 - cs_ref[1]) * nsend
        j = pl.program_id(0)

        @pl.when(jnp.logical_and(j >= w0, j < w0 + nsend))
        def _():
            from repro.collectives.compression import pow2_scale
            scale = pow2_scale(jnp.max(jnp.abs(v), axis=1, keepdims=True)
                               / 127.0)
            sq_ref[...] = jnp.clip(jnp.round(v / scale), -127,
                                   127).astype(jnp.int8)
            ss_ref[...] = scale


def rs_step_kernel_q(buf, recv_q, recv_s, c, c_next=None, *,
                     interpret=False):
    """int8-wire twin of :func:`rs_step_kernel` (oracle:
    ``ref.rs_step_ref_q``).

    ``buf``: [2h] float32; ``recv_q``: [h] int8; ``recv_s``: [h // ch]
    float32 per-chunk scales (``ch = compression.wire_chunk(h)``).  Each
    grid block dequantizes its rows of the received payload, accumulates
    against the kept half in float32, and — with ``c_next`` given —
    re-quantizes its rows of the next outgoing half (per-codec-chunk
    scales computed in-block) in the same HBM pass: int8 stays on the
    wire, f32 only ever lives in the accumulation.

    The kernel works on a 2-D view with one codec chunk per row, so every
    scale is its row's ``[R, 1]`` entry and no reshape happens in-kernel.
    The send variant requires ``h % 512 == 0`` (both halves then chunk at
    256); compiled on the chip, rows come in whole 32-row tiles
    (``h % 8192 == 0``; ``ops`` runs other rank blocks on the shmap path).
    """
    from repro.collectives import compression as comp

    h = recv_q.shape[0]
    assert buf.shape == (2 * h,), (buf.shape, h)
    ch = comp.wire_chunk(h)
    assert recv_s.shape == (h // ch,), (recv_s.shape, h, ch)
    rows = h // ch
    if c_next is None:
        rb = _pow2_divisor(rows, Q_ROWS)
        cs = jnp.stack([jnp.asarray(c, jnp.int32)])
    else:
        assert h % 512 == 0, (
            f"rs_step_kernel_q send variant needs h % 512 == 0, got {h}")
        rb = _pow2_divisor(rows // 2, Q_ROWS)
        cs = jnp.stack([jnp.asarray(c, jnp.int32),
                        jnp.asarray(c_next, jnp.int32)])
    nch = rows // rb
    rowblock = pl.BlockSpec((rb, ch), lambda j, cs: (j, 0))
    scaleblock = pl.BlockSpec((rb, 1), lambda j, cs: (j, 0))
    out_specs = [rowblock]
    out_shape = [_out((rows, ch), jnp.float32, buf)]
    if c_next is not None:
        send = _send_map(nch // 2)
        out_specs += [pl.BlockSpec((rb, ch), lambda j, cs: send(j, cs) + (0,)),
                      pl.BlockSpec((rb, 1), lambda j, cs: send(j, cs) + (0,))]
        out_shape += [_out((rows // 2, ch), jnp.int8, buf),
                      _out((rows // 2, 1), jnp.float32, buf)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nch,),
        in_specs=[
            pl.BlockSpec((rb, ch), lambda j, cs: (cs[0] * nch + j, 0)),
            rowblock,
            scaleblock,
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        functools.partial(_rs_step_q_body, nsend=nch // 2),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
    )(cs, buf.reshape(2 * rows, ch), recv_q.reshape(rows, ch),
      recv_s.reshape(rows, 1))
    if c_next is None:
        return out[0].reshape(h)
    newbuf, q, sc = out
    return newbuf.reshape(h), q.reshape(h // 2), sc.reshape(rows // 2)


# ---------------------------------------------------------------------------
# Allgather step: fused c-ordered merge
# ---------------------------------------------------------------------------

def _ag_step_body(cs_ref, buf_ref, recv_ref, out_ref, *, nch):
    # block copies only: no arithmetic on packed (bf16/int8) vregs
    j = pl.program_id(0)
    c = cs_ref[0]
    use_buf = jnp.logical_and(j >= c * nch, j < (c + 1) * nch)

    @pl.when(use_buf)
    def _():
        out_ref[...] = buf_ref[...]

    @pl.when(jnp.logical_not(use_buf))
    def _():
        out_ref[...] = recv_ref[...]


def ag_step_kernel(buf, recv, c, *, interpret=False):
    """buf, recv: [h] -> merged [2h] = [buf, recv] if c == 0 else
    [recv, buf], written in one placement pass (no concat temporaries).

    Any dtype, ``h % 128 == 0``.  The kernel works on the payload's
    ``(h // 128, 128)`` row view, which on the chip is the flat vector's
    own tiled layout when ``h`` is whole tiles (1024 32-bit, 2048 16-bit,
    4096 8-bit elements), so the view costs no copy."""
    h = buf.shape[0]
    assert recv.shape == (h,) and h % LANES == 0, (buf.shape, recv.shape)
    rows = h // LANES
    rb = _pow2_divisor(rows, AG_ROWS)
    nch = rows // rb
    cs = jnp.stack([jnp.asarray(c, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(2 * nch,),
        in_specs=[
            pl.BlockSpec((rb, LANES),
                         lambda j, cs: (jnp.clip(j - cs[0] * nch, 0,
                                                 nch - 1), 0)),
            pl.BlockSpec((rb, LANES),
                         lambda j, cs: (jnp.clip(j - (1 - cs[0]) * nch, 0,
                                                 nch - 1), 0)),
        ],
        out_specs=pl.BlockSpec((rb, LANES), lambda j, cs: (j, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_ag_step_body, nch=nch), grid_spec=grid_spec,
        out_shape=_out((2 * rows, LANES), buf.dtype, buf),
        interpret=interpret,
    )(cs, buf.reshape(rows, LANES), recv.reshape(rows, LANES))
    return out.reshape(2 * h)


# ---------------------------------------------------------------------------
# Ring step: in-place block update (aliased read-modify-write)
# ---------------------------------------------------------------------------

def _ring_update_body(s_ref, v_ref, recv_ref, out_ref, upd_ref=None):
    r = v_ref[...] + recv_ref[...]
    out_ref[...] = r
    if upd_ref is not None:
        upd_ref[...] = r


def _ring_write_body(s_ref, v_ref, recv_ref, out_ref):
    out_ref[...] = recv_ref[...]


def ring_update_kernel(v, recv, ridx, *, accumulate: bool = True,
                       return_updated: bool = False,
                       interpret=False):
    """v: [p*b]; recv: [b] -> v with block ``ridx`` ``+= recv`` (or
    ``= recv``).  The output aliases ``v``: only block ``ridx``'s chunks
    are revised, the other p-1 blocks never cross HBM.

    With ``return_updated=True`` (reduce-scatter path) the kernel also
    emits the updated block as a second output — which *is* the next ring
    step's outgoing chunk (``send_{t+1}`` reads the block ``ridx_t`` this
    step just wrote), so the per-step send slice disappears entirely.
    """
    b = recv.shape[0]
    assert v.shape[0] % b == 0, (v.shape, b)
    chunk = _pow2_divisor(b)
    nchb = b // chunk
    s = jnp.stack([jnp.asarray(ridx, jnp.int32)])
    in_specs = [
        pl.BlockSpec((chunk,), lambda j, s: (s[0] * nchb + j,)),
        pl.BlockSpec((chunk,), lambda j, s: (j,)),
    ]
    v_out_spec = pl.BlockSpec((chunk,), lambda j, s: (s[0] * nchb + j,))
    if not accumulate:
        assert not return_updated  # AG: the next send is recv itself
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nchb,), in_specs=in_specs,
            out_specs=v_out_spec)
        return pl.pallas_call(
            _ring_write_body, grid_spec=grid_spec,
            out_shape=_out(v.shape, v.dtype, v),
            input_output_aliases={1: 0}, interpret=interpret,
        )(s, v, recv)
    if not return_updated:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nchb,), in_specs=in_specs,
            out_specs=v_out_spec)
        return pl.pallas_call(
            _ring_update_body, grid_spec=grid_spec,
            out_shape=_out(v.shape, v.dtype, v),
            input_output_aliases={1: 0}, interpret=interpret,
        )(s, v, recv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nchb,), in_specs=in_specs,
        out_specs=[v_out_spec,
                   pl.BlockSpec((chunk,), lambda j, s: (j,))],
    )
    return pl.pallas_call(
        _ring_update_body, grid_spec=grid_spec,
        out_shape=[_out(v.shape, v.dtype, v), _out((b,), v.dtype, v)],
        input_output_aliases={1: 0}, interpret=interpret,
    )(s, v, recv)


# ---------------------------------------------------------------------------
# Fused matmul + block-permute (matmul+RS pack / AG+matmul unpack)
# ---------------------------------------------------------------------------

def _mm_body(perm_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_call(x, w, perm, lhs_perm: bool, *, bm, bn, bk, interpret):
    m, k = x.shape
    n = w.shape[1]
    nb = perm.shape[0]
    rows = m // nb
    bm = _pow2_divisor(rows, bm)
    bn = _pow2_divisor(n, bn)
    bk = _pow2_divisor(k, bk)
    nm, nn, nk = m // bm, n // bn, k // bk
    tpb = rows // bm  # row tiles per permutation block

    def permrow(i, perm_ref):
        return perm_ref[i // tpb] * tpb + i % tpb

    if lhs_perm:
        x_map = lambda i, j, kk, p: (permrow(i, p), kk)
        o_map = lambda i, j, kk, p: (i, j)
    else:
        x_map = lambda i, j, kk, p: (i, kk)
        o_map = lambda i, j, kk, p: (permrow(i, p), j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bk, bn), lambda i, j, kk, p: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out_dtype = jnp.result_type(x, w)
    return pl.pallas_call(
        functools.partial(_mm_body, nk=nk), grid_spec=grid_spec,
        out_shape=_out((m, n), out_dtype, x),
        interpret=interpret,
    )(jnp.asarray(perm, jnp.int32), x, w)


def matmul_pack_kernel(x, w, block_perm, *, bm: int = 128, bn: int = 128,
                       bk: int = 512, interpret=False):
    """Tiled ``x @ w`` whose output row-block ``b`` holds input row-block
    ``block_perm[b]``: the reduce-scatter pre-permute lands for free in the
    matmul's output writes.  ``m % len(block_perm) == 0``."""
    m = x.shape[0]
    nb = block_perm.shape[0] if hasattr(block_perm, "shape") else len(block_perm)
    assert m % nb == 0, (m, nb)
    # output block b = input block perm[b]  <=>  out index map uses inverse
    inv = jnp.argsort(jnp.asarray(block_perm, jnp.int32))
    return _mm_call(x, w, inv, lhs_perm=False, bm=bm, bn=bn, bk=bk,
                    interpret=interpret)


def gather_matmul_kernel(xg, w, block_perm, *, bm: int = 128, bn: int = 128,
                         bk: int = 512, interpret=False):
    """Tiled ``xg[block_perm] @ w`` (row blocks): the allgather's final
    un-permute is folded into the LHS reads, never materialized."""
    m = xg.shape[0]
    nb = block_perm.shape[0] if hasattr(block_perm, "shape") else len(block_perm)
    assert m % nb == 0, (m, nb)
    return _mm_call(xg, w, jnp.asarray(block_perm, jnp.int32), lhs_perm=True,
                    bm=bm, bn=bn, bk=bk, interpret=interpret)
