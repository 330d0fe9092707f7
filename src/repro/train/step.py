"""The training step: partial-auto shard_map with Bine gradient collectives.

Distribution (DESIGN.md Sec. 5):
  * manual axes = the DP ranks (("pod","data") on the multi-pod mesh) —
    gradient reduce-scatter, optimizer update on 1/n_dp shards (ZeRO-1),
    and parameter allgather all run on OUR schedules (Bine by default);
  * auto axis = "model" — tensor-parallel collectives lower through GSPMD
    from with_sharding_constraint hints.

The rank order of the flattened ("pod","data") axis is pod-major, so rank
distance ≈ pod locality: exactly the paper's block-placement assumption,
and the lever that lets distance-doubling Bine reduce-scatter keep its
*largest* messages inside a pod while only the smallest cross the DCN.

Backends: bine (paper) | recdoub (binomial butterflies) | ring | xla
(psum_scatter/all_gather) | bine_hier (Sec. 6.2: intra-pod first) |
pallas_fused (the bine schedule with every step's local slice/add/concat
chain fused into one Pallas kernel — ``repro.kernels.collectives``; fp32
bit-for-bit with the bine shmap path) | auto (resolves via the topology
decision table, including to pallas_fused).

Gradient bucketing (``train/buckets.py``): by default the ZeRO-sharded
leaves are packed into large flat wire buckets — ONE reduce-scatter and
ONE allgather per bucket instead of per leaf — so the per-collective
α·log₂(p) latency is paid O(buckets) times, not O(leaves) times, and
``backend="auto"`` prices large uniform payloads (where the paper's
large-vector schedules and ``pallas_fused`` win) instead of hundreds of
tiny ones.  The AdamW update runs on per-leaf views of each bucket's
reduced row; the update of bucket ``i`` is independent dataflow from the
allgather of bucket ``i-1``, so XLA can overlap them.  The packing
preserves element ownership, which makes the bucketed step fp32
**bit-for-bit identical** to the per-leaf path for the deterministic
backends (bine/recdoub/ring/pallas_fused).  ``TrainConfig.bucket_bytes``:
-1 (default) sizes buckets from the topology decision table, 0 disables
(per-leaf path), >0 is an explicit wire-dtype capacity in bytes.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.obs  # noqa: F401  (its compile counter sees the step compile)
from repro.collectives import shmap
from repro.models import transformer as T
from repro.models.sharding import constrain_params, param_specs
from repro.optim.adamw import AdamWConfig, adamw_init_leaf, adamw_update_leaf, lr_at
from repro.train import buckets, zero


#: wire dtypes TrainConfig accepts — "auto" resolves per bucket via the
#: joint (backend, wire) decision table (topology.select_wire)
WIRE_DTYPES = ("float32", "bfloat16", "int8", "auto")

#: backends with an int8 wire-codec path (mirrors cost.WIRE_CODEC_BACKENDS)
_CODEC_BACKENDS = ("bine", "recdoub", "pallas_fused")


@dataclass(frozen=True)
class TrainConfig:
    backend: str = "bine"            # bine | recdoub | ring | xla | bine_hier
    #                                # | pallas_fused | auto
    dp_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    accum_steps: int = 1
    clip_norm: float = 1.0
    #: gradient/param wire compression: float32 | bfloat16 (cast) | int8
    #: (pow2-scale wire codec + error feedback, bucketed path only) | auto
    #: (per-bucket joint (backend, wire) table lookup)
    wire_dtype: str = "float32"
    adamw: AdamWConfig = AdamWConfig()
    #: decision-table preset consulted when backend == "auto"
    topology: str = "tpu_multipod"
    #: table provenance for backend == "auto": "analytic" (cost model) or
    #: "measured" (empirical tuner cells merged over it; repro.tuner)
    tuning: str = "analytic"
    #: small/large allreduce switch (inclusive), bytes of the wire dtype
    small_cutoff_bytes: int = 16384
    #: gradient-bucket capacity in wire-dtype bytes: -1 (default) reads the
    #: per-topology choice cached in the decision table, 0 disables
    #: bucketing (per-leaf collectives), >0 is an explicit capacity
    bucket_bytes: int = -1
    #: whether the step may run Mosaic kernels; :func:`for_mesh` clears it
    #: on meshes where the step's shard_map stays partially manual
    fused_kernels: bool = True

    def __post_init__(self):
        # Fail at construction, not silently mid-step: the old _wire_cast
        # fell through to a plain astype for any dtype it did not know,
        # shipping e.g. a float16 wire with no decode or mean-scaling.
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unsupported wire_dtype {self.wire_dtype!r}: expected one "
                f"of {WIRE_DTYPES}")
        if self.wire_dtype == "int8":
            if self.backend not in _CODEC_BACKENDS + ("auto",):
                raise ValueError(
                    f"wire_dtype='int8' needs a codec-capable backend "
                    f"{_CODEC_BACKENDS} or 'auto', got {self.backend!r}")
            if self.bucket_bytes == 0:
                raise ValueError(
                    "wire_dtype='int8' runs on the bucketed flat-vector "
                    "path; bucket_bytes=0 disables bucketing")

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    @property
    def opt_dp_order(self) -> Tuple[str, ...]:
        # bine_hier reduce-scatters data-first (intra-pod first), producing a
        # data-major block layout along the zero dim.
        if self.backend == "bine_hier" and len(self.dp_axes) > 1:
            return tuple(reversed(self.dp_axes))
        return self.dp_axes


# ---------------------------------------------------------------------------
# Gradient collectives (bucketed flat + per-leaf dim-general)
# ---------------------------------------------------------------------------

def _backend_for_bytes(tcfg: TrainConfig, collective: str, p: int,
                       nbytes: int) -> str:
    """Concrete backend for a gradient collective of ``nbytes`` payload.

    backend="auto" consults the topology decision table at trace time
    (static shapes; zero runtime cost) with the flattened DP rank count
    and the FULL-vector payload (the table's byte convention) — the
    general mechanism that replaces the old hard-coded element-count
    cutoff.  Shared by the in-step dispatch (``_backend_for``) and the
    out-of-step ``bucket_backends`` introspection so the two can never
    drift."""
    if tcfg.backend != "auto":
        return tcfg.backend
    from repro.topology import select_backend
    return _runnable(tcfg, select_backend(collective, p, nbytes,
                                          tcfg.topology, tuning=tcfg.tuning))


def _runnable(tcfg: TrainConfig, backend: str) -> str:
    """An auto-resolved backend the step can run: without Mosaic kernels
    (``fused_kernels=False``) pallas_fused runs as bine — the same
    schedule, as shmap ops."""
    if backend == "pallas_fused" and not tcfg.fused_kernels:
        return "bine"
    return backend


def _backend_for(tcfg: TrainConfig, collective: str, arr,
                 gathered: bool = False) -> str:
    """``_backend_for_bytes`` for one traced array inside the shard_map.

    ``gathered=True`` marks call sites whose ``arr`` is one rank's shard
    (the allgather input), scaled up by the DP size."""
    if tcfg.backend != "auto":
        return tcfg.backend
    p = shmap.axis_size(tcfg.dp_axes)
    nbytes = arr.size * arr.dtype.itemsize * (p if gathered else 1)
    return _backend_for_bytes(tcfg, collective, p, nbytes)


def _wire_cast(tcfg: TrainConfig, g, n_dp: int):
    """Cast one gradient leaf to the wire dtype (per-leaf/replicated path).

    bf16 wire pre-scales by ``1/n_dp`` BEFORE the reduce: the sum of
    ``n_dp`` large bf16 gradients can overflow to inf before the post-hoc
    mean division (bf16 shares fp32's exponent range, but accumulating in
    bf16 reaches it ``n_dp``× sooner).  ``n_dp`` is a power of two, so the
    pre-scale is exact (an exponent shift) and costs no precision.  The
    fp32 path is untouched — it divides after the reduce, bit-compatible
    with the pre-bucketing step.

    ``int8``/``auto`` leaves stay float32 here: the wire codec only runs
    on the bucketed flat path (``_bucket_wire_cast``); per-leaf and
    replicated collectives are plain f32.  Anything else raises —
    ``TrainConfig.__post_init__`` enforces the same set, so a step can
    never silently ship an uncoded wire dtype (the old astype
    fall-through)."""
    wire = tcfg.wire_dtype
    if wire == "bfloat16":
        return (g / n_dp).astype(jnp.bfloat16)
    if wire in ("float32", "int8", "auto"):
        return g.astype(jnp.float32)
    raise ValueError(f"unsupported wire_dtype {wire!r}")


def _post_reduce_div(tcfg: TrainConfig, n_dp: int) -> float:
    """What the reduced wire value still must be divided by for the mean."""
    return 1.0 if tcfg.wire_dtype == "bfloat16" else float(n_dp)


def _bucket_wire_cast(wire: str, g, n_dp: int):
    """``_wire_cast`` for one bucket's RESOLVED wire dtype.

    int8 pre-scales by the exact ``1/n_dp`` exponent shift like bf16 —
    the codec then quantizes mean-scale values, so its per-chunk scales
    (and the error-feedback residual) are in gradient-mean units."""
    if wire == "bfloat16":
        return (g / n_dp).astype(jnp.bfloat16)
    if wire == "int8":
        return g.astype(jnp.float32) / n_dp
    return g.astype(jnp.float32)


def _bucket_post(wire: str, n_dp: int) -> float:
    """Post-reduce divisor for one bucket's resolved wire dtype."""
    return 1.0 if wire in ("bfloat16", "int8") else float(n_dp)


def _rs_leaf(tcfg: TrainConfig, g, zd: int, n_dp: int):
    """Reduce over DP ranks; scatter along zd (or full allreduce if zd<0)."""
    axes = tcfg.dp_axes
    wire = _wire_cast(tcfg, g, n_dp)
    if zd < 0:
        b = _backend_for(tcfg, "allreduce", wire)
        if b == "xla":
            return lax.psum(wire, axes)
        if b == "ring":
            return shmap.allreduce_ring(wire, axes)
        if b == "bine_hier" and len(axes) > 1:
            return shmap.allreduce_hierarchical(wire, axes[1:], axes[0], "bine")
        algo = {"bine": "bine", "recdoub": "recdoub"}.get(b, "bine")
        # inclusive boundary, matching CollectiveConfig.small_cutoff_bytes
        if wire.size * wire.dtype.itemsize <= tcfg.small_cutoff_bytes:
            return shmap.allreduce_small(wire, axes, algo)
        if b == "pallas_fused":
            from repro.kernels import collectives as fused
            return fused.allreduce(wire, axes, "bine")
        return shmap.allreduce_butterfly(wire, axes, algo)
    b = _backend_for(tcfg, "reduce_scatter", wire)
    if b == "xla":
        return lax.psum_scatter(wire, axes, scatter_dimension=zd, tiled=True)
    if b == "bine_hier" and len(axes) > 1:
        # intra-pod (data) first: the big messages stay on ICI
        out = wire
        for ax in reversed(axes):          # data, then pod
            out = shmap.reduce_scatter_dim(out, zd, ax, "bine")
        return out
    if b == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.reduce_scatter_dim(wire, zd, axes, "bine")
    algo = {"bine": "bine", "recdoub": "recdoub", "ring": "ring"}[b]
    return shmap.reduce_scatter_dim(wire, zd, axes, algo)


def _ag_leaf(tcfg: TrainConfig, x, zd: int):
    """Inverse allgather along zd over the DP ranks."""
    if zd < 0:
        return x
    axes = tcfg.dp_axes
    b = _backend_for(tcfg, "allgather", x, gathered=True)
    if b == "xla":
        return lax.all_gather(x, axes, axis=zd, tiled=True)
    if b == "bine_hier" and len(axes) > 1:
        out = x
        for ax in axes:                    # pod, then data (inverse order)
            out = shmap.allgather_dim(out, zd, ax, "bine")
        return out
    if b == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.allgather_dim(x, zd, axes, "bine")
    algo = {"bine": "bine", "recdoub": "recdoub", "ring": "ring"}[b]
    return shmap.allgather_dim(x, zd, axes, algo)


def _rs_bucket(tcfg: TrainConfig, v, backend: Optional[str] = None):
    """One flat reduce-scatter: full bucket vector -> this rank's row.

    The backend is resolved per BUCKET (``backend="auto"`` prices the
    bucket's full payload, not a leaf's), mirroring ``_rs_leaf``'s
    dispatch on a flat vector; bine_hier runs the same intra-pod-first
    axis sequence as the per-leaf path, so block ownership matches the
    ``opt_dp_order`` shard layout.  ``backend`` overrides the resolution
    (the bucketed step passes its static ``bucket_decisions``)."""
    axes = tcfg.dp_axes
    b = backend if backend is not None \
        else _backend_for(tcfg, "reduce_scatter", v)
    if b == "xla":
        p = shmap.axis_size(axes)
        return lax.psum_scatter(v.reshape(p, -1), axes, scatter_dimension=0,
                                tiled=False).reshape(-1)
    if b == "bine_hier" and len(axes) > 1:
        out = v
        for ax in reversed(axes):          # data, then pod
            out = shmap.reduce_scatter(out, ax, "bine")
        return out
    if b == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.reduce_scatter(v, axes, "bine")
    algo = {"bine": "bine", "bine_hier": "bine", "recdoub": "recdoub",
            "ring": "ring"}[b]
    return shmap.reduce_scatter(v, axes, algo)


def _rs_bucket_q(backend: str, axes, v):
    """Int8-wire flat reduce-scatter: f32 bucket vector -> decoded f32 row.

    Dispatches the codec'd twins (``shmap.reduce_scatter_q`` /
    ``kernels.collectives.reduce_scatter_q``), which are bit-identical to
    each other — the backend choice changes speed, never the decode."""
    if backend == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.reduce_scatter_q(v, axes, "bine")
    return shmap.reduce_scatter_q(v, axes, backend)


def _ag_bucket_q(backend: str, axes, row):
    """Int8-wire flat allgather: this rank's row -> decoded f32 vector,
    identical on every rank (quantize-once / move / dequantize-once)."""
    if backend == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.allgather_q(row, axes, "bine")
    return shmap.allgather_q(row, axes, backend)


def _ag_bucket(tcfg: TrainConfig, row, backend: Optional[str] = None):
    """Inverse flat allgather: this rank's row -> the full bucket vector."""
    axes = tcfg.dp_axes
    b = backend if backend is not None \
        else _backend_for(tcfg, "allgather", row, gathered=True)
    if b == "xla":
        return lax.all_gather(row, axes, axis=0, tiled=True)
    if b == "bine_hier" and len(axes) > 1:
        out = row
        for ax in axes:                    # pod, then data (inverse order)
            out = shmap.allgather(out, ax, "bine")
        return out
    if b == "pallas_fused":
        from repro.kernels import collectives as fused
        return fused.allgather(row, axes, "bine")
    algo = {"bine": "bine", "bine_hier": "bine", "recdoub": "recdoub",
            "ring": "ring"}[b]
    return shmap.allgather(row, axes, algo)


def _small_allreduce(tcfg: TrainConfig, x):
    # scalars/metric stacks always take the small full-vector path —
    # nothing to fuse, so pallas_fused shares bine's tree here
    b = _backend_for(tcfg, "allreduce", x)
    if b == "xla":
        return lax.psum(x, tcfg.dp_axes)
    if b == "ring":
        # the butterfly trees are pow2-only; ring pads to any p — the
        # path a non-pow2 survivor set (resilience.elastic) trains on
        return shmap.allreduce_ring(x, tcfg.dp_axes)
    algo = "recdoub" if b == "recdoub" else "bine"
    return shmap.allreduce_small(x, tcfg.dp_axes, algo)


def resolve_bucket_plan(tcfg: TrainConfig, n_dp: int, params_shapes,
                        layout) -> Optional[buckets.BucketPlan]:
    """The step's static bucket plan (None = bucketing disabled).

    Capacity resolution: ``tcfg.bucket_bytes`` > 0 verbatim, -1 reads the
    per-topology ``bucket_bytes`` entry cached in the decision table
    (``topology.select_bucket_bytes``), 0 — or a single DP rank — turns
    bucketing off.  Deterministic across processes: static shapes only.
    """
    if n_dp <= 1 or tcfg.bucket_bytes == 0:
        return None          # before the table lookup — nothing to size
    cap = tcfg.bucket_bytes
    if cap < 0:
        from repro.topology import select_bucket_bytes
        cap = select_bucket_bytes(n_dp, tcfg.topology, tuning=tcfg.tuning)
    # effective wire width: fractional for int8 (scale metadata included);
    # "auto" sizes conservatively at f32 — a bucket planned at 4 B/elem
    # never overfills whatever wire the per-bucket decision later picks
    from repro.collectives.compression import WIRE_BYTES_PER_ELEM
    wire_itemsize = WIRE_BYTES_PER_ELEM.get(tcfg.wire_dtype, 4.0)
    plan = buckets.plan_buckets(params_shapes, layout, n_dp, cap,
                                wire_itemsize)
    return plan if plan.buckets else None


def _bucket_decision(tcfg: TrainConfig, collective: str, p: int,
                     f32_bytes: int, wire_bytes: int) -> Tuple[str, str]:
    """Joint ``(backend, wire_dtype)`` for one bucket collective.

    ``wire_dtype="auto"`` asks the decision table's joint wire rows
    (``topology.select_wire``) at the bucket's f32 payload; a pinned
    backend keeps its choice and takes the wire only if codec-capable.
    Explicit wire dtypes price the backend at the actual wire payload
    (the pre-codec behavior); an auto-resolved non-codec backend under
    explicit int8 snaps to "bine" — the codec family's default — rather
    than dropping the compression the user asked for."""
    wire = tcfg.wire_dtype
    if wire == "auto":
        if p & (p - 1):
            # codec butterflies need a power-of-two rank count; non-pow2
            # meshes stay uncompressed rather than faulting mid-trace
            return _backend_for_bytes(tcfg, collective, p, f32_bytes), \
                "float32"
        from repro.topology import select_wire
        b, w = select_wire(collective, p, f32_bytes, tcfg.topology,
                           tuning=tcfg.tuning)
        b = _runnable(tcfg, b)
        if tcfg.backend != "auto":
            b = tcfg.backend
            if b not in _CODEC_BACKENDS:
                w = "float32"
        return b, w
    b = _backend_for_bytes(tcfg, collective, p, wire_bytes)
    if wire == "int8" and b not in _CODEC_BACKENDS:
        b = "bine"
    return b, wire


def bucket_decisions(tcfg: TrainConfig, plan: buckets.BucketPlan):
    """Static per-bucket ``(rs_backend, rs_wire, ag_backend, ag_wire)``.

    The RS decision prices the bucket's gradient payload, the AG its
    param-dtype payload.  The allgather wire only ever goes int8 — a
    bf16-resolved AG falls back to the plain param-dtype gather (params
    already travel at their own dtype; a lossy extra cast has no codec
    path to decode it)."""
    p = plan.n_dp
    out = []
    for b in plan.buckets:
        f32_rs = b.nbytes(4.0, p)
        rs_wire_bytes = b.nbytes(plan.wire_itemsize, p)
        ag_bytes = b.nbytes(np.dtype(b.dtype).itemsize, p)
        rs_b, rs_w = _bucket_decision(tcfg, "reduce_scatter", p, f32_rs,
                                      rs_wire_bytes)
        ag_b, ag_w = _bucket_decision(tcfg, "allgather", p, ag_bytes,
                                      ag_bytes)
        if ag_w == "bfloat16":
            ag_w = "float32"
        out.append((rs_b, rs_w, ag_b, ag_w))
    return out


def bucket_backends(tcfg: TrainConfig, plan: buckets.BucketPlan):
    """Concrete (reduce_scatter, allgather) backend per bucket — the
    backend projection of ``bucket_decisions``, so introspection and the
    step's dispatch can never drift."""
    return [(rs_b, ag_b)
            for rs_b, _, ag_b, _ in bucket_decisions(tcfg, plan)]


def bucket_report(tcfg: TrainConfig, plan: Optional[buckets.BucketPlan]):
    """Per-bucket decision report for the dryrun/monitoring paths.

    One row per wire bucket: the resolved (reduce_scatter, allgather)
    backend at the bucket's payload — through the SAME resolver the step
    dispatches with — plus where each decision came from: ``"measured"``
    or ``"analytic"`` table cells under ``backend="auto"``, ``"fixed"``
    when the backend is pinned by config.  This is the report the tuner's
    end-to-end test asserts on: after ``launch/tune.py`` populates a
    measured table, a ``tuning="measured"`` step's buckets must show
    measured provenance.
    """
    if plan is None:
        return []
    rows = []
    for i, (b, (rs_b, rs_w, ag_b, ag_w)) in enumerate(
            zip(plan.buckets, bucket_decisions(tcfg, plan))):
        rs_bytes = b.nbytes(plan.wire_itemsize, plan.n_dp)
        ag_bytes = b.nbytes(np.dtype(b.dtype).itemsize, plan.n_dp)
        if tcfg.backend == "auto":
            from repro.topology import decision_provenance
            rs_src = decision_provenance("reduce_scatter", plan.n_dp,
                                         rs_bytes, tcfg.topology,
                                         tuning=tcfg.tuning)
            ag_src = decision_provenance("allgather", plan.n_dp, ag_bytes,
                                         tcfg.topology, tuning=tcfg.tuning)
        else:
            rs_src = ag_src = "fixed"
        if tcfg.wire_dtype == "auto":
            from repro.topology import wire_decision_provenance
            f32_rs = b.nbytes(4.0, plan.n_dp)
            rs_wsrc = wire_decision_provenance(
                "reduce_scatter", plan.n_dp, f32_rs, tcfg.topology,
                tuning=tcfg.tuning)
            ag_wsrc = wire_decision_provenance(
                "allgather", plan.n_dp, ag_bytes, tcfg.topology,
                tuning=tcfg.tuning)
        else:
            rs_wsrc = ag_wsrc = "fixed"
        rows.append({
            "bucket": i, "n_leaves": len(b.slots),
            "rs_backend": rs_b, "rs_bytes": rs_bytes, "rs_provenance": rs_src,
            "rs_wire": rs_w, "rs_wire_provenance": rs_wsrc,
            "ag_backend": ag_b, "ag_bytes": ag_bytes, "ag_provenance": ag_src,
            "ag_wire": ag_w, "ag_wire_provenance": ag_wsrc,
        })
    return rows


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------

def _ef_init(tcfg: TrainConfig, plan: Optional[buckets.BucketPlan]
             ) -> Dict[str, Any]:
    """Zero error-feedback residuals, one per int8-wire bucket.

    Each leaf is this rank's LOCAL ``(1, L)`` row (``L`` = the bucket's
    full flat length): the residual corrects the rank's own pre-collective
    contribution, so it is per-rank data sharded ``P(dp)`` along dim 0 —
    global shape ``(n_dp, L)``.  float32 always (see ``ef_compress``).
    Empty dict when no bucket compresses — the state tree then carries no
    ``"ef"`` key at all, keeping f32/bf16 checkpoints unchanged."""
    if plan is None:
        return {}
    return {str(b.bid): jnp.zeros((1, b.row_elems * plan.n_dp), jnp.float32)
            for b, d in zip(plan.buckets, bucket_decisions(tcfg, plan))
            if d[1] == "int8"}


def init_train_state(model_cfg, tcfg: TrainConfig, params, n_dp: int,
                     dp_rank: Optional[int] = None):
    """Build (sharded) optimizer state.

    Host-side path (dp_rank given): slice leaves for one rank.
    SPMD path (dp_rank None): call under shard_map/jit where params are the
    global view; slicing is expressed as reduce-scatter of params later, so
    here we slice with static indexing per rank via axis_index (manual).
    """
    layout = zero.zero_layout(model_cfg, params, n_dp)

    def one(p, zd):
        if zd < 0 or dp_rank is None:
            return adamw_init_leaf(p)
        return adamw_init_leaf(zero.slice_leaf(p, zd, n_dp, dp_rank))

    opt = jax.tree.map(one, params, layout)
    state = {"opt": opt, "step": jnp.zeros((), jnp.int32)}
    ef = _ef_init(tcfg, resolve_bucket_plan(tcfg, n_dp, params, layout))
    if ef:
        state["ef"] = ef
    return state


def init_train_state_spmd(model_cfg, tcfg: TrainConfig, params, n_dp: int):
    """Init opt shards inside shard_map: slice each leaf at this rank."""
    layout = zero.zero_layout(model_cfg, params, n_dp)
    ranks = shmap.axis_index(tcfg.opt_dp_order)

    def one(p, zd):
        if zd < 0:
            return adamw_init_leaf(p)
        k = p.shape[zd] // n_dp
        sl = lax.dynamic_slice_in_dim(p, ranks * k, k, axis=zd)
        return adamw_init_leaf(sl)

    opt = jax.tree.map(one, params, layout)
    state = {"opt": opt, "step": jnp.zeros((), jnp.int32)}
    ef = _ef_init(tcfg, resolve_bucket_plan(tcfg, n_dp, params, layout))
    if ef:
        state["ef"] = ef
    return state


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

def for_mesh(tcfg: TrainConfig, mesh) -> TrainConfig:
    """``tcfg`` as the step runs it on ``mesh``.

    With a non-DP axis above 1 the step's shard_map leaves that axis to
    GSPMD, and Mosaic kernels cannot be partitioned there: an explicit
    ``pallas_fused`` is refused, and ``auto`` runs bine wherever its
    table picks pallas_fused."""
    if _fully_manual(tcfg, mesh):
        return tcfg
    if tcfg.backend == "pallas_fused":
        raise ValueError(
            "backend='pallas_fused' needs every non-DP mesh axis to have "
            f"size 1 (mesh {dict(mesh.shape)}): its Mosaic kernels cannot "
            "run in a shard_map that leaves the model axis to GSPMD.  Use "
            "backend='bine' (the same schedule) or 'auto'.")
    return tcfg.replace(fused_kernels=False)


def make_train_step(model_cfg, tcfg: TrainConfig, mesh, params_shapes):
    """Returns (jitted step, in/out shardings dict).

    step(params, state, batch) -> (params, state, metrics)
    """
    tcfg = for_mesh(tcfg, mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in tcfg.dp_axes]))
    if n_dp > 1:
        from repro.collectives.api import executable_at
        if not executable_at(tcfg.backend, n_dp):
            # fail at build time with the fix, not mid-trace inside a
            # ppermute: the butterfly schedules need a pow2 DP axis
            raise ValueError(
                f"backend={tcfg.backend!r} cannot execute at non-power-of-"
                f"two n_dp={n_dp} (butterfly schedules need pow2 axes; "
                f"the non-pow2 adapters are plan/price-level only).  Use "
                f"backend='ring' or 'xla', or derive the config via "
                f"repro.resilience.elastic.elastic_train_config, which "
                f"picks the executable fallback for a survivor set.")
    from repro.models import sharding as _sh
    _sh.set_model_parallel(mesh.shape.get(tcfg.model_axis, 1))
    layout = zero.zero_layout(model_cfg, params_shapes, n_dp)
    pspecs = param_specs(model_cfg, params_shapes)
    plan = resolve_bucket_plan(tcfg, n_dp, params_shapes, layout)
    if tcfg.wire_dtype == "int8":
        if n_dp & (n_dp - 1):
            raise ValueError(
                f"wire_dtype='int8' needs a power-of-two DP rank count "
                f"(the codec schedules are butterfly-only), got {n_dp}")
        if plan is None and n_dp > 1:
            raise ValueError(
                "wire_dtype='int8' needs the bucketed path; this model has "
                "no bucketable (ZeRO-sharded) leaves")
    decisions = None if plan is None else bucket_decisions(tcfg, plan)
    if decisions is not None:
        # telemetry: the step's static per-bucket dispatches, once per build
        from repro.obs import collect as _obs_collect
        _obs_collect.record_bucket_plan(tcfg, plan, decisions, n_dp)
    ef_bids = [] if plan is None else [
        str(b.bid) for b, d in zip(plan.buckets, decisions)
        if d[1] == "int8"]

    dp = tcfg.dp_axes if len(tcfg.dp_axes) > 1 else tcfg.dp_axes[0]

    def body(params, state, batch, ranks):
        # ranks[a] is this shard's index along manual axis a, passed as data
        # (a sharded arange) — see shmap.axis_index_hints.
        with shmap.axis_index_hints({a: r[0] for a, r in ranks.items()}):
            return _body_inner(params, state, batch)

    def _body_inner(params, state, batch):
        params = constrain_params(model_cfg, params)
        opt, step = state["opt"], state["step"]

        # ---- forward/backward (optionally microbatched) ----
        def lfn(p, mb):
            loss, metrics = T.loss_fn(p, model_cfg, mb)
            return loss, metrics

        if tcfg.accum_steps > 1:
            A = tcfg.accum_steps
            mbs = jax.tree.map(
                lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:]), batch)

            def acc_body(carry, mb):
                g_acc, me_acc = carry
                (loss, me), g = jax.value_and_grad(lfn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                me_acc = jax.tree.map(lambda a, b: a + b, me_acc, me)
                return (g_acc, me_acc), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            me0 = {"loss": 0., "ce": 0., "z_loss": 0., "aux_loss": 0.,
                   "tokens": 0.}
            me0 = jax.tree.map(jnp.float32, me0)
            (grads, metrics), _ = lax.scan(acc_body, (g0, me0), mbs)
            grads = jax.tree.map(lambda g: g / A, grads)
            metrics = jax.tree.map(lambda m: m / A, metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                lfn, has_aux=True)(params, batch)

        # ---- DP gradient reduce-scatter (the paper's collectives) ----
        # Bucketed by default: sharded leaves pack into flat wire buckets,
        # ONE flat RS per bucket; the per-leaf views sliced from each
        # bucket's reduced row are bit-identical to what per-leaf
        # reduce_scatter_dim would produce (ownership-preserving layout).
        flat_p, treedef = jax.tree.flatten(params)
        flat_opt = treedef.flatten_up_to(opt)
        flat_gr = treedef.flatten_up_to(grads)
        flat_zd = treedef.flatten_up_to(layout)
        post = _post_reduce_div(tcfg, n_dp)
        g_sh: list = [None] * len(flat_p)
        new_ef: Dict[str, Any] = {}
        if plan is None:
            for i, (g, zd) in enumerate(zip(flat_gr, flat_zd)):
                g_sh[i] = _rs_leaf(tcfg, g, zd, n_dp).astype(
                    jnp.float32) / post
        else:
            for i in plan.replicated:
                g_sh[i] = _rs_leaf(tcfg, flat_gr[i], -1, n_dp).astype(
                    jnp.float32) / post
            for bucket, (rs_b, rs_w, _, _) in zip(plan.buckets, decisions):
                with jax.named_scope(f"rs/b{bucket.bid}"):
                    v = buckets.pack_bucket(
                        bucket,
                        [_bucket_wire_cast(rs_w, flat_gr[s.index], n_dp)
                         for s in bucket.slots], n_dp)
                    if rs_w == "int8":
                        # error feedback: quantization error this rank's
                        # wire codec will commit lands in the residual and
                        # rides into next step's gradient (wire_int8 = the
                        # SAME codec, so the first re-encode on the wire is
                        # lossless and the residual is exact for it)
                        from repro.collectives import compression as comp
                        sent, res = comp.ef_compress(
                            v, state["ef"][str(bucket.bid)][0],
                            codec="wire_int8")
                        new_ef[str(bucket.bid)] = res[None]
                        row = _rs_bucket_q(rs_b, tcfg.dp_axes, sent)
                    else:
                        row = _rs_bucket(tcfg, v, backend=rs_b)
                    row = row.astype(jnp.float32) / _bucket_post(rs_w, n_dp)
                    for s, view in zip(bucket.slots, buckets.shard_views(
                            bucket, row, n_dp)):
                        g_sh[s.index] = view

        # ---- grad-norm + metrics: ONE fused small allreduce ----
        # (was 6 scalar allreduces: 5 metrics + the grad-norm square)
        with jax.named_scope("grad_norm"):
            sq_shard = sum(jnp.sum(jnp.square(g)) for g, zd in zip(
                g_sh, flat_zd) if zd >= 0)
            sq_repl = sum(jnp.sum(jnp.square(g)) for g, zd in zip(
                g_sh, flat_zd) if zd < 0)
            mkeys = sorted(metrics)
            stacked = jnp.stack(
                [jnp.asarray(sq_shard, jnp.float32)]
                + [jnp.asarray(metrics[k], jnp.float32) for k in mkeys])
            red = _small_allreduce(tcfg, stacked)
            gnorm = jnp.sqrt(red[0] + sq_repl)
            scale = jnp.minimum(1.0, tcfg.clip_norm / (gnorm + 1e-9)) \
                if tcfg.clip_norm > 0 else jnp.ones(())

        # ---- sharded AdamW + parameter allgather ----
        lr = lr_at(tcfg.adamw, step)

        @jax.named_scope("adamw")
        def upd(i):
            new_master, st2 = adamw_update_leaf(
                tcfg.adamw, flat_opt[i], g_sh[i] * scale, step, lr)
            return new_master.astype(flat_p[i].dtype), st2

        new_p: list = [None] * len(flat_p)
        new_opt: list = [None] * len(flat_p)
        if plan is None:
            for i, zd in enumerate(flat_zd):
                master, new_opt[i] = upd(i)
                new_p[i] = _ag_leaf(tcfg, master, zd)
        else:
            for i in plan.replicated:
                new_p[i], new_opt[i] = upd(i)
            # per bucket: per-leaf updates on the bucket's views, then ONE
            # flat allgather.  Bucket i's update chain shares no dataflow
            # with bucket i-1's allgather, so XLA is free to overlap them.
            for bucket, (_, _, ag_b, ag_w) in zip(plan.buckets, decisions):
                masters = []
                for s in bucket.slots:
                    master, new_opt[s.index] = upd(s.index)
                    masters.append(master)
                with jax.named_scope(f"ag/b{bucket.bid}"):
                    packed = buckets.pack_shards(bucket, masters)
                    if ag_w == "int8":
                        # int8 param allgather: quantization error does NOT
                        # compound — every step re-derives the wire value
                        # from the exact f32 master, and all ranks decode
                        # the same bits (quantize-once / move /
                        # dequantize-once)
                        full = _ag_bucket_q(
                            ag_b, tcfg.dp_axes, packed).astype(
                                jnp.dtype(bucket.dtype))
                    else:
                        full = _ag_bucket(tcfg, packed, backend=ag_b)
                    for s, leaf in zip(bucket.slots, buckets.unpack_bucket(
                            bucket, full, n_dp)):
                        new_p[s.index] = leaf
        new_params = jax.tree.unflatten(treedef, new_p)
        new_opt = jax.tree.unflatten(treedef, new_opt)
        new_params = constrain_params(model_cfg, new_params)

        metrics = {k: red[j + 1] / n_dp for j, k in enumerate(mkeys)}
        metrics["grad_norm"] = gnorm
        metrics["lr"] = lr
        new_state = {"opt": new_opt, "step": step + 1}
        if ef_bids:
            new_state["ef"] = new_ef
        return new_params, new_state, metrics

    # ---- specs ----
    param_in = jax.tree.map(lambda _: P(), params_shapes)
    opt_manual = jax.tree.map(
        lambda leaf, zd: {k: zero.shard_spec_manual(leaf.ndim, zd,
                                                    tcfg.opt_dp_order)
                          for k in ("master", "m", "v")},
        params_shapes, layout)
    state_in = {"opt": opt_manual, "step": P()}
    if ef_bids:
        # EF residual: per-rank rows, global (n_dp, L), sharded on dim 0
        state_in["ef"] = {bid: P(dp) for bid in ef_bids}
    batch_in = jax.tree.map(lambda _: P(dp), {"inputs": 0, "targets": 0})
    metrics_out = P()

    rank_in = {a: P(a) for a in tcfg.dp_axes}
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_in, state_in, batch_in, rank_in),
        out_specs=(param_in, state_in,
                   {"loss": metrics_out, "ce": metrics_out,
                    "z_loss": metrics_out, "aux_loss": metrics_out,
                    "tokens": metrics_out, "grad_norm": metrics_out,
                    "lr": metrics_out}),
        axis_names=_manual_axes(tcfg, mesh), check_vma=False)

    def stepped(params, state, batch):
        ranks = _rank_arrays(tcfg, mesh)
        return smapped(params, state, batch, ranks)

    # outer-jit shardings (also used by the dry-run's ShapeDtypeStructs)
    def ns(spec):
        return NamedSharding(mesh, spec)

    opt_sharding = jax.tree.map(
        lambda leaf, spec, zd: {
            k: ns(_merge_spec(spec, zd, tcfg.opt_dp_order, leaf.ndim))
            for k in ("master", "m", "v")},
        params_shapes, pspecs, layout)
    state_sharding = {"opt": opt_sharding, "step": ns(P())}
    if ef_bids:
        state_sharding["ef"] = {bid: ns(P(dp)) for bid in ef_bids}
    shardings = {
        "params": jax.tree.map(lambda s: ns(s), pspecs),
        "state": state_sharding,
        "batch": {"inputs": ns(P(dp)), "targets": ns(P(dp))},
        # advisory, like serve's collective plan: the static bucket plan
        # this step traced with (None = per-leaf collectives)
        "bucket_plan": plan,
    }
    jitted = jax.jit(stepped, donate_argnums=(0, 1))
    return jitted, shardings, layout


def _rank_arrays(tcfg: TrainConfig, mesh):
    """Per-axis arange inputs backing shmap.axis_index_hints."""
    return {a: jnp.arange(mesh.shape[a], dtype=jnp.int32)
            for a in tcfg.dp_axes}


def _fully_manual(tcfg: TrainConfig, mesh) -> bool:
    """True when every non-DP mesh axis has size 1: nothing is left for
    GSPMD to partition, so the step's shard_map goes manual over all axes
    (Mosaic kernels refuse to lower in a partially manual region)."""
    return all(n == 1 for a, n in mesh.shape.items()
               if a not in tcfg.dp_axes)


def _manual_axes(tcfg: TrainConfig, mesh):
    """Manual axes of the step's shard_map: the DP axes, with the model
    axis left to GSPMD, or every axis when :func:`_fully_manual`."""
    if _fully_manual(tcfg, mesh):
        return set(mesh.axis_names)
    return set(tcfg.dp_axes)


def _merge_spec(model_spec, zd: int, dp_axes, ndim: int):
    out = list(tuple(model_spec) + (None,) * (ndim - len(tuple(model_spec))))
    if zd >= 0:
        out[zd] = tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]
    return P(*out)


def make_init_fns(model_cfg, tcfg: TrainConfig, mesh, params_shapes):
    """jitted init of params (replicated over DP, model-sharded) and of the
    sharded train state (opt shards built in-place, no full fp32 copy)."""
    tcfg = for_mesh(tcfg, mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in tcfg.dp_axes]))
    from repro.models import sharding as _sh
    _sh.set_model_parallel(mesh.shape.get(tcfg.model_axis, 1))
    param_in = jax.tree.map(lambda _: P(), params_shapes)
    layout = zero.zero_layout(model_cfg, params_shapes, n_dp)
    opt_manual = jax.tree.map(
        lambda leaf, zd: {k: zero.shard_spec_manual(leaf.ndim, zd,
                                                    tcfg.opt_dp_order)
                          for k in ("master", "m", "v")},
        params_shapes, layout)
    state_out = {"opt": opt_manual, "step": P()}
    plan = resolve_bucket_plan(tcfg, n_dp, params_shapes, layout)
    if plan is not None:
        dp = tcfg.dp_axes if len(tcfg.dp_axes) > 1 else tcfg.dp_axes[0]
        efs = {str(b.bid): P(dp)
               for b, d in zip(plan.buckets, bucket_decisions(tcfg, plan))
               if d[1] == "int8"}
        if efs:
            state_out["ef"] = efs

    def init_p(key):
        return constrain_params(model_cfg, T.init_params(key, model_cfg))

    def init_s(params, ranks):
        with shmap.axis_index_hints({a: r[0] for a, r in ranks.items()}):
            return init_train_state_spmd(model_cfg, tcfg, params, n_dp)

    init_params_fn = jax.jit(init_p)
    rank_in = {a: P(a) for a in tcfg.dp_axes}
    smapped_init = jax.shard_map(
        init_s, mesh=mesh, in_specs=(param_in, rank_in),
        out_specs=state_out,
        axis_names=_manual_axes(tcfg, mesh), check_vma=False)
    init_state_fn = jax.jit(
        lambda params: smapped_init(params, _rank_arrays(tcfg, mesh)))
    return init_params_fn, init_state_fn
