"""Seeded weights in the layout the served program loads, made on the
device in one jitted call and in the type they are served in.

The benchmark, not the program, makes the weights: the reference
(``references/``) reads the same arrays, so nothing it compares against
was produced by the code under test.  The model's own weights come from
its architecture module, ``references/<reference>.py``, whose
``make_weights(dims, keys)`` draws one key per leaf from the front of a
stream of ``N_KEYS`` split from the seed; the Medusa heads, which every
architecture serves alike, take the next two.  ``check_layout`` holds
the layout to what the program's own initializer would build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# keys split from the seed; an architecture that needs more leaves than
# this splits one of them further
N_KEYS = 32


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, 32 bits or more."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype) * scale).astype(dtype)


def around_one(key, shape, dtype):
    # norm scales near 1, not exactly 1, so the check sees them
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
        dtype)


def medusa_heads(dims: dict, keys) -> dict:
    """The Medusa heads: ``dims["medusa_heads"]`` residual projections of
    d_model, N(0, 0.02^2), and their outputs over the padded vocabulary,
    N(0, 1/d_model)."""
    dt = jnp.dtype(dims["dtype"])
    d, vp, H = dims["d_model"], dims["vocab_padded"], dims["medusa_heads"]
    return {"w": normal(next(keys), (H, d, d), 0.02, dt),
            "out": normal(next(keys), (H, d, vp), d ** -0.5, dt)}


def build(arch, dims: dict, seed: int):
    """(params, heads) for ``seed`` in one compiled call (the key is an
    argument, so every seed reuses one program); ``arch`` is the
    architecture module."""
    def make(key):
        keys = iter(jax.random.split(key, N_KEYS))
        params = arch.make_weights(dims, keys)
        return params, medusa_heads(dims, keys)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


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
