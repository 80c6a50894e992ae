"""Shared numeric building blocks: norms, RoPE, inits, online-softmax merge.

Everything is pure-functional jnp; params are nested dicts of arrays.
Per-layer parameter stacks (leading L axis) are built with vmap'd inits so
model stacks can ``lax.scan`` over layers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # mask value (finite: avoids NaN from (-inf) - (-inf))


# --------------------------------------------------------------------------
# inits
# --------------------------------------------------------------------------
def dense_init(rng, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab, d, dtype):
    return (jax.random.normal(rng, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim, theta, yarn):
    """YaRN's inverse frequencies (transformers' ``_compute_yarn_parameters``
    with truncation), as float64 NumPy: with ``corr(r) = d ln(L0 / (2 pi
    r)) / (2 ln theta)``, ``low = floor(corr(beta_fast))`` and ``high =
    ceil(corr(beta_slow))``, frequency i blends ``theta_i / factor`` and
    ``theta_i`` by ``ramp_i = clip((i - low) / (high - low), 0, 1)``."""
    d = head_dim
    inv = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))

    def corr(rot):
        return d * math.log(yarn.original_max_position / (2 * math.pi * rot)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def rope_tables(cfg):
    """Per-layer rotary operands ``(inv_freq (L, hd/2), scale (L,))`` of a
    stack with ``layer_types``: plain RoPE at ``rope_theta`` on sliding
    layers, YaRN (``rope_yarn``) on full ones; ``scale`` multiplies cos and
    sin."""
    from repro.configs.base import FULL
    plain = 1.0 / (float(cfg.rope_theta) ** (
        np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim))
    rows, scales = [], []
    for kind in cfg.layer_types:
        if kind == FULL and cfg.rope_yarn is not None:
            rows.append(yarn_freqs(cfg.head_dim, cfg.rope_theta,
                                   cfg.rope_yarn))
            scales.append(cfg.rope_yarn.attention_factor)
        else:
            rows.append(plain)
            scales.append(1.0)
    return (jnp.asarray(np.stack(rows), jnp.float32),
            jnp.asarray(scales, jnp.float32))


def apply_rope(x, positions, theta, rope=None):
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  ``rope``
    = ``(inv_freq (hd/2,), scale ())``, one layer's row of
    ``rope_tables``, replaces the plain table at ``theta``."""
    hd = x.shape[-1]
    if rope is None:
        freqs, scale = rope_freqs(hd, theta), None      # (hd/2,)
    else:
        freqs, scale = rope
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention math (pure-jnp reference; Pallas kernels mirror this in kernels/)
# --------------------------------------------------------------------------
def gqa_scores(q, k):
    """q: (B, S, Hq, hd), k: (B, T, Hkv, hd) -> scores (B, Hq, S, T)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, S, Hkv, g, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    return s.reshape(B, Hq, S, k.shape[1])


def gqa_attend(q, k, v, mask, scale):
    """Reference masked attention.  mask: broadcastable (B, 1|Hq, S, T) bool."""
    s = gqa_scores(q, k) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    B, Hq, S, T = s.shape
    Hkv = v.shape[2]
    g = Hq // Hkv
    pg = p.reshape(B, Hkv, g, S, T)
    o = jnp.einsum("bkgst,btkd->bskgd", pg, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, v.shape[-1]).astype(v.dtype)


def gqa_attend_partial(q, k, v, mask, scale):
    """Attention partials for online-softmax merging (the paper's Eq.-1 split).

    Returns (o_unnormalized, m, l):
      m (B,Hq,S): running max; l (B,Hq,S): sum exp(s-m); o: sum exp(s-m) @ v.
    Merging partials from different units/shards:
      m* = max(m_i); l* = sum l_i e^{m_i-m*}; o* = sum o_i e^{m_i-m*}; out = o*/l*.
    """
    s = gqa_scores(q, k) * scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,Hq,S)
    # all-masked rows: keep m finite
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    B, Hq, S, T = s.shape
    Hkv = v.shape[2]
    g = Hq // Hkv
    pg = p.reshape(B, Hkv, g, S, T)
    o = jnp.einsum("bkgst,btkd->bskgd", pg, v.astype(jnp.float32))
    o = o.reshape(B, S, Hq, v.shape[-1])
    return o, m_safe, l


def merge_partials_carry(carry, part):
    """Fold one (o, m, l) partial into an accumulator (blocked attention)."""
    o0, m0, l0 = carry
    o1, m1, l1 = part
    m_new = jnp.maximum(m0, m1)
    c0 = jnp.exp(m0 - m_new)
    c1 = jnp.exp(m1 - m_new)
    l_new = l0 * c0 + l1 * c1
    o_new = (o0 * jnp.transpose(c0, (0, 2, 1))[..., None]
             + o1 * jnp.transpose(c1, (0, 2, 1))[..., None])
    return o_new, m_new, l_new


def merge_partials(parts):
    """Merge a list of (o, m, l) online-softmax partials -> normalized output.

    o: (B,S,Hq,hd) fp32 unnormalized, m/l: (B,Hq,S).
    """
    ms = jnp.stack([m for _, m, _ in parts])                  # (P,B,Hq,S)
    m_star = jnp.max(ms, axis=0)
    o_star = 0.0
    l_star = 0.0
    for o, m, l in parts:
        corr = jnp.exp(m - m_star)                            # (B,Hq,S)
        l_star = l_star + l * corr
        o_star = o_star + o * jnp.transpose(corr, (0, 2, 1))[..., None]
    l_star = jnp.maximum(l_star, 1e-30)
    return o_star * (1.0 / jnp.transpose(l_star, (0, 2, 1))[..., None])


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def stack_init(rng, n, init_fn):
    """vmap an init over n layer rngs -> stacked params (leading axis n)."""
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def layer_scan(cfg, body, carry, xs):
    """lax.scan over stacked layers, or a Python unroll when
    ``cfg.unroll_layers`` (dry-run cost-correction lowers: XLA's
    cost_analysis counts a while-loop body ONCE, so scanned stacks
    under-report FLOPs/bytes by ~L; launch/dryrun.py lowers unrolled L=1/L=2
    variants and extrapolates — see EXPERIMENTS.md §Roofline methodology)."""
    if not getattr(cfg, "unroll_layers", False):
        return jax.lax.scan(body, carry, xs)
    length = len(jax.tree_util.tree_leaves(xs)[0])
    ys = []
    for i in range(length):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    return carry, ys
