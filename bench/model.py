"""A configuration file as the program runs it, and its weights.

``model_config`` maps a ``bench/configs/*.json`` file onto the program's
``ModelConfig``; ``init_tree`` makes the weights from a key in the
program's parameter layout, in the type they are served and trained in
(bfloat16).  The plain reference regenerates them with the same function,
so it takes no weights from the program.
"""

from __future__ import annotations


def dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "nh": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "f": c["intermediate_size"], "V": c["vocab_size"],
            "L": c["num_hidden_layers"], "tied": c["tie_word_embeddings"],
            "qk_norm": c.get("qk_norm", False),
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"]}


def model_config(c: dict, **train):
    """The program's ``ModelConfig`` for configuration ``c``; ``train``
    sets its training knobs (``z_loss``)."""
    from repro.configs.base import ModelConfig
    k = dims(c)
    if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling"):
        raise ValueError(f"{c['name']}: the program applies full RoPE "
                         f"without scaling")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=k["L"], d_model=k["d"],
        n_heads=k["nh"], n_kv_heads=k["nkv"], head_dim=k["hd"],
        d_ff=k["f"], vocab_size=k["V"], act="swiglu", qk_norm=k["qk_norm"],
        rope_theta=float(k["theta"]), norm_eps=float(k["eps"]),
        tie_embeddings=k["tied"], dtype=c["dtype"], cache_dtype=c["dtype"],
        **train)


def _layer(c: dict, key):
    import jax
    import jax.numpy as jnp
    k = dims(c)
    d, nh, nkv, hd, f = k["d"], k["nh"], k["nkv"], k["hd"], k["f"]
    ks = jax.random.split(key, 11)

    def w(i, shape, scale):
        return (jax.random.normal(ks[i], shape, jnp.float32) * scale
                ).astype(jnp.dtype(c["dtype"]))

    attn = {"wq": w(0, (d, nh * hd), d ** -0.5),
            "wk": w(1, (d, nkv * hd), d ** -0.5),
            "wv": w(2, (d, nkv * hd), d ** -0.5),
            "wo": w(3, (nh * hd, d), (nh * hd) ** -0.5)}
    if k["qk_norm"]:
        attn["q_norm"] = w(4, (hd,), 0.1)
        attn["k_norm"] = w(5, (hd,), 0.1)
    return {"ln1": w(6, (d,), 0.1), "ln2": w(7, (d,), 0.1), "attn": attn,
            "mlp": {"wi": w(8, (d, f), d ** -0.5),
                    "wg": w(9, (d, f), d ** -0.5),
                    "wo": w(10, (f, d), f ** -0.5)}}


def init_tree(c: dict, key) -> dict:
    """Weights of configuration ``c`` from ``key``: N(0, 1/fan_in)
    matrices, N(0, 0.02^2) embeddings, N(0, 0.1^2) norm offsets (the program
    scales by ``1 + w``), layers stacked on a leading axis."""
    import jax
    import jax.numpy as jnp
    k = dims(c)
    dt = jnp.dtype(c["dtype"])
    ke, kl, kn, kh = jax.random.split(key, 4)
    layers = jax.vmap(lambda i: _layer(c, jax.random.fold_in(kl, i)))(
        jnp.arange(k["L"]))
    tree = {"embed": (jax.random.normal(ke, (k["V"], k["d"]), jnp.float32)
                      * 0.02).astype(dt),
            "segments": [layers],
            "final_norm": (jax.random.normal(kn, (k["d"],), jnp.float32)
                           * 0.1).astype(dt)}
    if not k["tied"]:
        tree["lm_head"] = (jax.random.normal(kh, (k["d"], k["V"]),
                                             jnp.float32)
                           * k["d"] ** -0.5).astype(dt)
    return tree


def weights_fn(c: dict):
    """One jitted call that makes the weights on the device from a
    32-bit seed.  The program and the reference both take their weights
    from this one program, so their values agree to the bit (a generator
    fused into another program may round differently)."""
    import jax
    import numpy as np
    f = jax.jit(lambda s: init_tree(c, jax.random.key(s)))
    return lambda seed: f(np.uint32(seed))


def tree_shapes(c: dict):
    import jax
    import jax.numpy as jnp
    return jax.eval_shape(lambda s: init_tree(c, jax.random.key(s)),
                          jnp.uint32(0))
