"""``bench/flops.py`` against sums by hand for both architectures, and
its weight count against the program's parameter shapes."""

import math

import jax
import numpy as np

from bench import core, flops
from bench.model import tree_shapes


def cfg(name):
    return core.load_json(core.BENCH / "configs" / f"{name}.json")


def test_phi4_mini_by_hand():
    c = cfg("phi4-mini-3.8b")
    d, f, V = 3072, 8192, 200064
    layer = d * 3072 + 2 * d * 1024 + 3072 * d + 3 * d * f
    L = c["num_hidden_layers"]
    assert flops.layer_matmul_params(c) == layer == 100_663_296
    assert flops.matmul_params(c) == L * layer + d * V
    T = 2048
    want = 6 * (L * layer + d * V) + L * 6 * 24 * 128 * (T + 1)
    assert flops.train_flops_per_token(c, T) == want
    full = c
    n = 32 * layer + d * V
    assert flops.decode_flops(full, ctx=1000, n_active=2) == \
        2 * n * 2 + 32 * 4 * 24 * 128 * 1000
    norms = 32 * 2 * d + d
    assert flops.decode_bytes(full, ctx=1000, n_active=2) == \
        (n + norms) * 2 + 1000 * 32 * 2 * 8 * 128 * 2


def test_qwen3_share_by_hand():
    c = cfg("qwen3-32b.vocab8-stage")
    d, f, V = 5120, 25600, 151936 // 8
    layer = d * 8192 + 2 * d * 1024 + 8192 * d + 3 * d * f
    assert flops.layer_matmul_params(c) == layer == 487_587_840
    assert flops.matmul_params(c) == layer + d * V
    assert math.isclose(flops.train_flops_per_token(c, 2048),
                        6 * (layer + d * V) + 6 * 64 * 128 * 2049)


def test_matmul_params_match_the_program_shapes():
    for name in ("phi4-mini-3.8b", "qwen3-32b.vocab8-stage"):
        c = cfg(name)
        shapes = tree_shapes(c)
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        names = [(jax.tree_util.keystr(p), x) for p, x in flat]
        mats = sum(int(np.prod(x.shape)) for n, x in names
                   if not any(k in n for k in ("embed", "norm", "ln1",
                                                "ln2")))
        head = c["hidden_size"] * c["vocab_size"] if c["tie_word_embeddings"] \
            else 0
        assert mats + head == flops.matmul_params(c), name
