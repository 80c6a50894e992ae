"""Seeded weights in the layout the served program loads, made on the
device in one jitted call and in the type they are served in.

The benchmark, not the program, makes the weights: the reference
(``references/``) reads the same arrays, so nothing it compares against
was produced by the code under test.  ``check_layout`` holds the layout to
what the program's own initializer would build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, 32 bits or more."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype) * scale).astype(dtype)


def _around_one(key, shape, dtype):
    # norm scales near 1, not exactly 1, so the check sees them
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
        dtype)


def make_weights(dims: dict, key):
    """(params, heads) for a dense GQA decoder with Medusa heads.

    ``dims``: d_model, n_layers, n_heads, n_kv_heads, head_dim, d_ff,
    vocab_padded, qkv_bias, tied, medusa_heads, dtype.  Projections are
    N(0, 1/fan_in); embeddings N(0, 0.02^2); qkv biases N(0, 0.1^2)."""
    dt = jnp.dtype(dims["dtype"])
    d, L, f = dims["d_model"], dims["n_layers"], dims["d_ff"]
    hd, hq, hkv = dims["head_dim"], dims["n_heads"], dims["n_kv_heads"]
    vp, H = dims["vocab_padded"], dims["medusa_heads"]
    ks = iter(jax.random.split(key, 32))
    attn = {
        "wq": _normal(next(ks), (L, d, hq * hd), d ** -0.5, dt),
        "wk": _normal(next(ks), (L, d, hkv * hd), d ** -0.5, dt),
        "wv": _normal(next(ks), (L, d, hkv * hd), d ** -0.5, dt),
        "wo": _normal(next(ks), (L, hq * hd, d), (hq * hd) ** -0.5, dt),
    }
    if dims["qkv_bias"]:
        attn["bq"] = _normal(next(ks), (L, hq * hd), 0.1, dt)
        attn["bk"] = _normal(next(ks), (L, hkv * hd), 0.1, dt)
        attn["bv"] = _normal(next(ks), (L, hkv * hd), 0.1, dt)
    params = {
        "embed": _normal(next(ks), (vp, d), 0.02, dt),
        "layers": {
            "ln1": _around_one(next(ks), (L, d), dt),
            "ln2": _around_one(next(ks), (L, d), dt),
            "attn": attn,
            "mlp": {
                "w_gate": _normal(next(ks), (L, d, f), d ** -0.5, dt),
                "w_up": _normal(next(ks), (L, d, f), d ** -0.5, dt),
                "w_down": _normal(next(ks), (L, f, d), f ** -0.5, dt),
            },
        },
        "ln_f": _around_one(next(ks), (d,), dt),
    }
    if not dims["tied"]:
        params["lm_head"] = _normal(next(ks), (d, vp), d ** -0.5, dt)
    heads = {"w": _normal(next(ks), (H, d, d), 0.02, dt),
             "out": _normal(next(ks), (H, d, vp), d ** -0.5, dt)}
    return params, heads


def build(dims: dict, seed: int):
    """Make the weights for ``seed`` in one compiled call (the key is an
    argument, so every seed reuses one program)."""
    fn = jax.jit(lambda k: make_weights(dims, k))
    return jax.block_until_ready(fn(seed_key(seed)))


def check_layout(ours, theirs) -> None:
    """Raise unless two pytrees agree in structure, shapes and dtypes."""
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"  bench:   {a}\n  program: {b}")
    for x, y in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"weight leaf {x.shape} {x.dtype} differs from "
                             f"the program's {y.shape} {y.dtype}")
