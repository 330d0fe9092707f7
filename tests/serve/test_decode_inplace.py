"""The decode step updates the K/V pool in place.

Each attention segment's stacked ``[n_layers, B, W, n_kv, hd]`` caches
ride the layer loop as its carry and every layer writes one row a slot
into them.  So the optimized ``decode_slots`` has no copy of a whole pool
leaf or of one layer's slab, and the donated pool comes back in the same
buffers.  The CPU compiler may widen the bf16 scatter to f32, so the HLO
check looks at ``copy`` ops and aliasing, not at every op of pool shape.

The numbers stay those of the full forward pass: per-slot positions that
differ across slots, gemma3's ring windows, the legacy scalar-``pos``
decode, and a slot past the end of its page, whose write is dropped.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.serve.engine import ServeConfig, make_serve_fns

#: the tolerance of ``test_decode_matches_forward_token_by_token``
TOL = dict(rtol=4e-2, atol=4e-2)


def _cfg(name):
    return base.reduced(base.get_config(name))


def _mesh():
    return make_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def _copy_shapes(txt):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in re.finditer(
                r"= \w+\[([\d,]*)\](?:\{[^}]*\})? copy\(", txt)}


def _aliases(txt):
    """``{output tuple index: parameter number}`` of the module's
    ``input_output_alias``."""
    head = txt.split("\n", 1)[0]
    return {int(o): int(p) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", head)}


def test_decode_slots_writes_the_pool_in_place():
    cfg = _cfg("phi4-mini-3.8b")
    mesh = _mesh()
    B, S = 8, 512
    fns = make_serve_fns(cfg, ServeConfig(), mesh, B, S)
    with jax.set_mesh(mesh):
        params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                                jax.random.key(0))
        pool = jax.eval_shape(fns.init_pool)
        txt = fns.decode_slots.lower(
            params, pool, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile().as_text()
    kv = [leaf for seg in pool["segments"] for leaf in (seg["k"], seg["v"])]
    pool_shapes = {tuple(a.shape) for a in kv}
    slab_shapes = {s[1:] for s in pool_shapes}
    assert pool_shapes == {(cfg.n_layers, B, S, cfg.n_kv_heads,
                            cfg.head_dim)}
    copies = _copy_shapes(txt)
    assert copies, "the copy pattern no longer matches the HLO text"
    assert not copies & pool_shapes, "a whole pool leaf is copied"
    assert not copies & slab_shapes, "a layer's slab is copied"
    # outputs are (logits, *pool leaves), arguments (*params, *pool, ...)
    n_params = len(jax.tree.leaves(params))
    alias = _aliases(txt)
    for j in range(len(jax.tree.leaves(pool))):
        assert alias.get(1 + j) == n_params + j, (j, alias)


def _run(case):
    """Decode a batch token by token; returns the forward pass' logits of
    each slot's sequence, each step's positions and logits, and the final
    pool and positions.

    Slots start empty and advance by a fixed random ``active`` mask, so
    their positions differ; a held slot is fed the token of its position,
    which rewrites the same K/V row.  Every slot runs the last step."""
    name, B, S, steps, scalar = {
        "per_slot": ("phi4-mini-3.8b", 4, 64, 24, False),
        "ring": ("gemma3-4b", 3, 48, 36, False),
        "scalar_pos": ("phi4-mini-3.8b", 2, 32, 20, True),
        "full_page": ("phi4-mini-3.8b", 3, 32, 33, False),
    }[case]
    cfg = _cfg(name)
    mesh = _mesh()
    fns = make_serve_fns(cfg, ServeConfig(), mesh, B, S)
    rng = np.random.RandomState(7)
    n_tok = -(-(steps + 1) // cfg.attn_chunk) * cfg.attn_chunk
    seqs = rng.randint(0, cfg.vocab_size, (B, n_tok)).astype(np.int32)
    if case == "full_page":
        # slot 0 runs every step: its last step is at pos == W
        active = rng.rand(steps, B) < 0.6
        active[:, 0] = True
    elif scalar:
        active = np.ones((steps, B), bool)
    else:
        active = rng.rand(steps, B) < 0.7
    # every slot's last write is then its newest position
    active[-1] = True
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: T.init_params(k, cfg))(jax.random.key(3))
        if scalar:
            pool = T.init_decode_state(cfg, B, S)
        else:
            pool = fns.init_pool()
        pos = np.zeros(B, np.int32)
        got, before = [], None
        for t in range(steps):
            toks = jnp.asarray(seqs[np.arange(B), pos][:, None])
            if case == "full_page" and t == steps - 1:
                assert pos[0] == S
                before = jax.tree.map(lambda a: np.asarray(a[:, 0]),
                                      pool["segments"])
            if scalar:
                logits, pool = fns.decode(params, pool, toks)
                logits = logits[:, 0]
            else:
                logits, pool = fns.decode_slots(
                    params, pool, toks, jnp.asarray(active[t], jnp.int32))
            got.append((pos.copy(), np.asarray(logits, np.float32)))
            pos = pos + active[t]
        full = np.asarray(jax.jit(lambda p, i: T.forward(p, cfg, i)[0])(
            params, jnp.asarray(seqs)), np.float32)
        pool = jax.tree.map(np.asarray, pool)
    return cfg, params, seqs, full, got, pool, pos, before, S


def _prefix_cache(cfg, params, seq):
    """The decode state the full forward pass leaves for ``seq`` (padded
    to the attention chunk; rows past ``len(seq)`` are padding)."""
    C = cfg.attn_chunk
    pad = np.zeros(-(-len(seq) // C) * C, np.int32)
    pad[:len(seq)] = seq
    _, st = jax.jit(lambda p, i, n: T.prefill(p, cfg, i, length=n))(
        params, jnp.asarray(pad[None]), jnp.int32(len(seq)))
    return jax.tree.map(lambda a: np.asarray(a[:, 0], np.float32),
                        st["segments"])


@pytest.mark.parametrize("case", ["per_slot", "ring", "scalar_pos",
                                  "full_page"])
def test_decode_slots_matches_forward(case):
    cfg, params, seqs, full, got, pool, pos, before, S = _run(case)
    B = seqs.shape[0]
    assert len(set(pos.tolist())) > 1 or case == "scalar_pos"
    for step_pos, logits in got:
        for b in range(B):
            p = step_pos[b]
            if p >= S:
                # past the end of its page: the write was dropped
                assert np.isfinite(logits[b]).all()
                continue
            np.testing.assert_allclose(logits[b], full[b, p], **TOL)
    np.testing.assert_array_equal(np.broadcast_to(pool["pos"], (B,)), pos)
    for b in range(B):
        n = min(int(pos[b]), S)
        if n == 0:
            continue
        ref = _prefix_cache(cfg, params, seqs[b, :n])
        for seg, rseg in zip(pool["segments"], ref):
            for key in ("k", "v"):
                a = np.asarray(seg[key][:, b], np.float32)
                m = min(n, a.shape[1])          # rows written so far
                np.testing.assert_allclose(a[:, :m], rseg[key][:, :m], **TOL)
    if case == "full_page":
        after = jax.tree.map(lambda a: np.asarray(a[:, 0]), pool["segments"])
        for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            assert np.array_equal(x, y), "a dropped write changed the page"
