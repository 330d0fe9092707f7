"""Model configuration dataclass + architecture registry (--arch <id>)."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    act: str = "swiglu"         # swiglu | geglu
    qk_norm: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    # attention pattern
    window: Optional[int] = None          # sliding-window size (None = full)
    local_global_ratio: int = 0           # k>0: k local layers per 1 global
    local_window: int = 1024
    # MoE
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    expert_shard: str = "expert"          # expert (EP) | ffn (TP inside expert)
    ep_blocks: int = 1                    # expert column-blocks: E*ep_blocks
                                          # stacked units shardable over model
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    attn_every: int = 0                   # zamba2: shared attn after every k blocks
    block_pattern: str = "transformer"    # transformer | xlstm | zamba
    # modality frontend stub
    frontend: Optional[str] = None        # audio | vision
    frontend_dim: int = 0                 # precomputed frame/patch feature dim
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    cache_dtype: str = "bfloat16"
    embed_scale: bool = False             # gemma-style sqrt(d) embed scaling
    # training-time knobs
    attn_chunk: int = 512                 # flash-style KV/Q chunking
    remat: bool = True
    z_loss: float = 1e-4
    aux_loss_weight: float = 1e-2         # MoE load-balance loss weight

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def n_params(self) -> int:
        """Exact parameter count via jax.eval_shape (no allocation)."""
        return _exact_params(self)

    @property
    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only top-k experts active)."""
        total = _exact_params(self)
        if self.n_experts == 0:
            return total
        # subtract the inactive experts' FFN weights
        ffn_mult = 3
        per_expert = ffn_mult * self.d_model * self.d_ff
        inactive = (self.n_experts - self.top_k) * per_expert * self.n_layers
        return total - inactive


import functools


@functools.lru_cache(maxsize=None)
def _exact_params_cached(cfg: ModelConfig) -> int:
    import jax
    import numpy as np
    from repro.models import transformer as T
    shapes = jax.eval_shape(
        lambda k: T.init_params(k, cfg), jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def _exact_params(cfg: ModelConfig) -> int:
    return _exact_params_cached(cfg)


def _count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    d, h = cfg.d_model, cfg.head_dim
    emb = cfg.vocab_size * d
    total = emb if cfg.tie_embeddings else 2 * emb
    if cfg.block_pattern == "xlstm":
        # per block: qkv-ish projections + gates + out; rough but consistent
        per = 0
        per += 4 * d * d  # mLSTM q,k,v,o projections (up-proj factor 2 folded)
        per += 4 * d      # gates
        total += cfg.n_layers * per
        return total
    att = d * (cfg.n_heads * h) + 2 * d * (cfg.n_kv_heads * h) \
        + (cfg.n_heads * h) * d
    ffn_mult = 3 if cfg.act in ("swiglu", "geglu") else 2
    ffn = ffn_mult * d * cfg.d_ff
    if cfg.block_pattern == "zamba":
        din = cfg.ssm_expand * d
        mamba = d * 2 * din + din * cfg.ssm_conv + \
            din * (2 * cfg.ssm_state) + din // cfg.ssm_head_dim * 2 + din * d \
            + d * cfg.d_ff * ffn_mult
        n_shared = max(1, cfg.n_layers // max(cfg.attn_every, 1))
        total += cfg.n_layers * mamba + (att + ffn)  # shared attn counted once
        return total
    if cfg.n_experts > 0:
        k = cfg.top_k if active_only else cfg.n_experts
        layer = att + k * ffn + d * cfg.n_experts  # + router
    else:
        layer = att + ffn
    total += cfg.n_layers * layer
    return total


_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    # load on a miss, not only when empty: a caller that imported one
    # config module directly has registered just that one
    if name not in _REGISTRY:
        _load_all()
    return _REGISTRY[name]


def list_configs():
    _load_all()
    return sorted(_REGISTRY)


def _load_all():
    from . import (gemma3_4b, gemma_7b, mixtral_8x7b, musicgen_medium,  # noqa
                   phi35_moe, phi4_mini, pixtral_12b, qwen3_32b,
                   xlstm_125m, zamba2_2p7b)


# ---------------------------------------------------------------------------
# Input shapes (assignment: 4 shapes per arch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

#: archs for which long_500k is runnable (sub-quadratic / bounded-window
#: attention); the rest skip it per the assignment (see DESIGN.md).
LONG_OK = ("xlstm-125m", "zamba2-2.7b", "mixtral-8x7b", "gemma3-4b")


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests: small layers/width,
    few experts, tiny vocab — but the SAME block pattern and features."""
    kw = dict(
        n_layers=min(cfg.n_layers, 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=256,
        attn_chunk=32,
        ssm_chunk=16,
        ssm_head_dim=16,
        ssm_state=min(cfg.ssm_state, 8) if cfg.ssm_state else 0,
        remat=False,
    )
    if cfg.n_experts > 0:
        kw["n_experts"] = 4
        kw["top_k"] = 2
    if cfg.local_global_ratio > 0:
        kw["n_layers"] = cfg.local_global_ratio + 2  # 1 full pattern + remainder
        kw["local_window"] = 16
    if cfg.window is not None:
        kw["window"] = 16
    if cfg.block_pattern == "zamba":
        kw["n_layers"] = 4
        kw["attn_every"] = 2
    if cfg.block_pattern == "xlstm":
        kw["n_layers"] = 5  # covers the mLSTM/sLSTM mix
    if cfg.frontend_dim:
        kw["frontend_dim"] = 16
    return cfg.replace(**kw)


def cell_is_runnable(arch: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch in LONG_OK
    return True
