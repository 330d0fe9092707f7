"""Sharding rules: parameter PartitionSpecs + activation constraints.

The distribution strategy (DESIGN.md Sec. 5):
  * the whole train/serve step runs inside a *partial-auto* shard_map —
    manual over the DP axes ("pod","data"), auto over "model" — so the
    gradient/optimizer collectives are OURS (Bine schedules over ppermute)
    while tensor-parallel collectives lower through GSPMD;
  * params carry `PartitionSpec`s over "model" only (DP replication is
    implicit in the manual axes);
  * activations are steered with `with_sharding_constraint`: the residual
    stream between layers is SEQUENCE-sharded over "model" (Megatron-SP
    style) so remat-saved carries stay 1/model_par per chip, and attention
    heads / ffn hidden / vocab logits are sharded over "model" inside each
    layer.

All specs mention ONLY the "model" axis: inside the partial-auto
shard_map the DP axes are manual and therefore invisible to GSPMD.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"

_ENABLED = True  # flipped off in pure-CPU single-device unit tests

#: distribution context, set by the step builders (train/serve/dryrun).
#: n_model == 1 means no tensor/sequence parallelism (unit tests).
_CTX = {"n_model": 1}


def set_model_parallel(n_model: int):
    _CTX["n_model"] = int(n_model)


def model_parallel() -> int:
    return _CTX["n_model"] if _ENABLED else 1


def strategy(cfg) -> str:
    """Per-arch layer parallelism strategy over the model axis.

    * ``megatron_sp`` — TP weights (column/row) + head-parallel attention +
      sequence-sharded residual stream.  Requires n_heads % n_model == 0.
    * ``pure_sp``     — sequence-parallel everything: tokens stay sharded
      over model through every projection, non-embedding weights are
      replicated (all pure_sp archs are <4B, so bf16 weights fit), and
      attention is chunked over the query grid.  Covers archs whose head
      counts do not divide the model axis (phi4 24H, musicgen 24H,
      gemma3 8H, xlstm 4H).
    """
    n = model_parallel()
    if n <= 1:
        return "single"
    if cfg.n_heads % n == 0 and cfg.d_model >= 1024:
        return "megatron_sp"
    return "pure_sp"


def shard(x, *spec):
    """Constrain activation sharding (model axis only).  Outside a mesh
    context (single-device unit tests) this is a no-op."""
    if not _ENABLED:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, TypeError, RuntimeError):
        return x


def seq_sharded(x):
    """Residual stream [B, T, d]: shard T over model (SP)."""
    return shard(x, None, MODEL_AXIS, None)


def head_sharded(x):
    """[B, T, H, hd]: shard heads over model."""
    return shard(x, None, None, MODEL_AXIS, None)


def ffn_sharded(x):
    """[B, T, F]: shard hidden over model."""
    return shard(x, None, None, MODEL_AXIS)


# ---------------------------------------------------------------------------
# Parameter spec rules: (leaf name, ndim) -> spec tuple.
# Column-shard input projections, row-shard output projections, shard
# expert / head / state dims.  Unmatched leaves are replicated.
# ---------------------------------------------------------------------------

_RULES: Dict[Tuple[str, int], Tuple] = {
    # embeddings / head
    ("embed", 2): (MODEL_AXIS, None),        # vocab-sharded
    ("lm_head", 2): (None, MODEL_AXIS),
    # attention (layers.init_attention)
    ("wq", 2): (None, MODEL_AXIS),
    ("wk", 2): (None, MODEL_AXIS),
    ("wv", 2): (None, MODEL_AXIS),
    ("wo", 2): (MODEL_AXIS, None),           # attn out [H*hd, d] / mlp out [F, d]
    # mlp (layers.init_mlp)
    ("wi", 2): (None, MODEL_AXIS),
    ("wg", 2): (None, MODEL_AXIS),
    # moe (moe.init_moe) — expert-block leaves [E*ep_blocks, d, ffb]:
    # the block stack shards over model (EP); router replicated
    ("router", 2): (None, None),
    ("wi", 3): (MODEL_AXIS, None, None),
    ("wg", 3): (MODEL_AXIS, None, None),
    ("wo", 3): (MODEL_AXIS, None, None),
    # mamba2 (ssm.init_mamba2) — channel TP: shard d_inner; B/C/dt (state
    # projections, shared across channels) replicated
    ("m_z", 2): (None, MODEL_AXIS),
    ("m_x", 2): (None, MODEL_AXIS),
    ("m_B", 2): (None, None),
    ("m_C", 2): (None, None),
    ("m_dt", 2): (None, None),
    ("conv_x", 2): (None, MODEL_AXIS),
    ("conv_B", 2): (None, None),
    ("conv_C", 2): (None, None),
    ("A_log", 1): (MODEL_AXIS,),
    ("D", 1): (MODEL_AXIS,),
    ("dt_bias", 1): (MODEL_AXIS,),
    ("out_proj", 2): (MODEL_AXIS, None),
    # mLSTM (ssm.init_mlstm): shard the 2x inner dim on up/gate/down projs;
    # block-diagonal q/k/v ([nh,hd,hd]) stay replicated (tiny).
    ("wup", 2): (None, MODEL_AXIS),
    ("wgate", 2): (None, MODEL_AXIS),
    ("down", 2): (MODEL_AXIS, None),
    # sLSTM (ssm.init_slstm): diagonal recurrence — shard units
    ("wz", 2): (None, MODEL_AXIS),
    ("ri", 1): (MODEL_AXIS,), ("rf", 1): (MODEL_AXIS,),
    ("rz", 1): (MODEL_AXIS,), ("ro", 1): (MODEL_AXIS,),
}

_EP_OVERRIDES: Dict[Tuple[str, int], Tuple] = {}  # EP is now the default

#: leaf names that can appear scan-stacked (leading period/layer dim)
_NORM_NAMES = {"norm", "norm2", "final_norm", "ln1", "ln2", "ln3",
               "q_norm", "k_norm"}


def _key_name(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def param_specs(cfg, params: Any) -> Any:
    """PartitionSpec pytree mirroring ``params``.

    Name+ndim matched; leaves under a dict named like a MoE block use the
    EP overrides when cfg.expert_shard == "expert".  Stacked (scan) leading
    dims shift specs right by one (the stack dim is never sharded over
    model).  Unmatched leaves (gates, norms, biases) are replicated.
    """
    ep = cfg.n_experts > 0 and cfg.expert_shard == "expert"
    strat = strategy(cfg)
    #: under pure_sp only the (vocab-dim) embedding/lm_head shard; every
    #: other weight is replicated and tokens shard over model instead.
    pure_sp_keep = {"embed", "lm_head"}

    def spec_for(path, leaf):
        names = [_key_name(k) for k in path]
        name = names[-1] if names else ""
        in_moe = any(n == "moe" for n in names)
        if name in _NORM_NAMES:
            return P(*((None,) * leaf.ndim))
        if strat == "pure_sp" and name not in pure_sp_keep:
            return P(*((None,) * leaf.ndim))
        # GQA with n_kv_heads < n_model: column-sharded K/V projections
        # cannot factor into whole heads (GSPMD would involuntarily
        # replicate mid-graph) — keep the small K/V weights replicated and
        # shard after the head repeat instead.
        if strat == "megatron_sp" and name in ("wk", "wv") and \
                cfg.n_kv_heads % max(model_parallel(), 1) != 0:
            nd = leaf.ndim - (1 if leaf.ndim == 3 else 0)
            if nd == 2:
                return P(*((None,) * leaf.ndim))
        for stacked in (0, 1):
            nd = leaf.ndim - stacked
            key = (name, nd)
            rules = _RULES
            if ep and in_moe and key in _EP_OVERRIDES:
                rules = {**_RULES, **_EP_OVERRIDES}
            if key in rules:
                return P(*(((None,) * stacked) + tuple(rules[key])))
        return P(*((None,) * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_for, params)


def constrain_params(cfg, params):
    """Apply the model-axis sharding constraints to a param pytree."""
    if not _ENABLED:
        return params
    specs = param_specs(cfg, params)

    def one(x, s):
        try:
            return jax.lax.with_sharding_constraint(x, s)
        except (ValueError, TypeError, RuntimeError):
            return x

    return jax.tree.map(one, params, specs)
