"""Pallas kernel: sparse-part-only tree attention (block-masked).

TPU-native counterpart of the paper's ARM COO SpMM (§III-B3): instead of
scalar gather/FMA over COO indices (which would idle the MXU), the W×W tree
correlation is computed as one VMEM-resident masked matmul.  Blocks and
operand layouts follow ``tree_attention`` (whole kv-head groups per block,
the tree mask pre-expanded to (G*W, W) int32).  Benchmarked in
benchmarks/sparse.py against (a) the naive per-element oracle and (b) the
dense-with-mask-over-everything strategy, mirroring Fig. 10b.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tree_attention import (_group_mask, _group_queries,
                                          _head_group, _pv, _scores,
                                          _ungroup)

NEG_INF = -1e30


def _tree_scores(q_ref, k_ref, mask_ref, h, scale):
    """Head ``h``'s masked (G*W, W) tree scores and the validity mask."""
    q = q_ref[0, h].astype(jnp.float32)            # (G*W, hd)
    k = k_ref[0, :, h, :].astype(jnp.float32)      # (W, hd)
    ok = mask_ref[...] != 0                        # (G*W, W)
    return jnp.where(ok, _scores(q, k) * scale, NEG_INF), ok


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale):
    for h in range(q_ref.shape[1]):
        s, ok = _tree_scores(q_ref, k_ref, mask_ref, h, scale)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(ok, jnp.exp(s - m), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        o_ref[0, h] = (_pv(p, v) / l).astype(o_ref.dtype)


def _partial_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *, scale):
    """Same masked matmul, emitting UNNORMALIZED online-softmax partials
    packed into one (G*W, hd + 2) block per head — o in [:, :hd], running
    max m at [:, hd], sum l at [:, hd + 1] — so the tree half merges with
    the paged cache walk (``tree_attention.paged_cache_attention``) via the
    Eq.-1 rule instead of being its own softmax island."""
    for h in range(q_ref.shape[1]):
        s, ok = _tree_scores(q_ref, k_ref, mask_ref, h, scale)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), NEG_INF / 2)
        p = jnp.where(ok, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        o_ref[0, h] = jnp.concatenate([_pv(p, v), m, l], axis=-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_tree_attention_partial(q, k_new, v_new, tree_mask, *, interpret):
    """q: (B, W, Hq, hd); returns merge partials ``(o (B, W, Hq, hd) f32
    unnormalized, m (B, Hq, W), l (B, Hq, W))`` in the
    ``cm.merge_partials`` layout (the W×W tree half of the split verify
    path)."""
    B, W, Hq, hd = q.shape
    Hkv = k_new.shape[2]
    G = Hq // Hkv
    hg = _head_group(Hkv)
    nh = Hkv // hg
    packed = pl.pallas_call(
        functools.partial(_partial_kernel, scale=hd ** -0.5),
        grid=(B, nh),
        in_specs=[
            pl.BlockSpec((1, hg, G * W, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((G * W, W), lambda b, h: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, G * W, hd + 2),
                               lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd + 2), jnp.float32),
        interpret=interpret,
        name="sparse_tree_attention_partial",
    )(_group_queries(q, Hkv), k_new, v_new, _group_mask(tree_mask, G))
    pk = packed.reshape(B, Hkv, G, W, hd + 2)
    o = pk[..., :hd].transpose(0, 3, 1, 2, 4).reshape(B, W, Hq, hd)
    m = pk[..., hd].reshape(B, Hkv * G, W)
    l = pk[..., hd + 1].reshape(B, Hkv * G, W)
    return o, m, l


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_tree_attention(q, k_new, v_new, tree_mask, *, interpret):
    """q: (B, W, Hq, hd); returns (B, W, Hq, hd) — sparse part only."""
    B, W, Hq, hd = q.shape
    Hkv = k_new.shape[2]
    G = Hq // Hkv
    hg = _head_group(Hkv)
    nh = Hkv // hg
    out = pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5),
        grid=(B, nh),
        in_specs=[
            pl.BlockSpec((1, hg, G * W, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, W, hg, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((G * W, W), lambda b, h: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hg, G * W, hd), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * W, hd), q.dtype),
        interpret=interpret,
        name="sparse_tree_attention",
    )(_group_queries(q, Hkv), k_new, v_new, _group_mask(tree_mask, G))
    return _ungroup(out, W)
