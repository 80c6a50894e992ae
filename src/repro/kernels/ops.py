"""jit'd public wrappers around the Pallas kernels.

Interpret mode follows the backend the caller is traced for: the kernels
compile through Mosaic on a TPU and run in the Pallas interpreter on the
CPU (the test backend).  Nothing else selects it, so a chip run never
interprets by accident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import sparse_tree as _sparse
from repro.kernels import tree_attention as _tree


def _interpret() -> bool:
    """True on the CPU backend, False on a TPU (and anywhere else, where
    the Mosaic lowering then fails loudly instead of interpreting)."""
    return jax.default_backend() == "cpu"


def tree_attention(q, ck, cv, k_new, v_new, key_pos, pos, tree_depth,
                   tree_mask, *, window=0, block_s=None):
    """Signature used by models/attention.py (backend="pallas").

    ``pos`` is () or (B,) and ``key_pos`` (S,) or (B, S): sequences sit at
    different absolute positions once batched speculative commits diverge,
    so the kernel takes per-batch ``q_pos``/``lo`` rows.
    """
    B = q.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    key_pos_b = jnp.broadcast_to(key_pos, (B, ck.shape[1]))
    q_pos = pos_b[:, None] + tree_depth[None, :].astype(jnp.int32)  # (B, W)
    if window:
        lo = q_pos - window
    else:
        lo = jnp.full_like(q_pos, -1)
    kwargs = {"interpret": _interpret()}
    if block_s:
        kwargs["block_s"] = block_s
    return _tree.tree_attention(q, ck, cv, k_new, v_new, key_pos_b, q_pos,
                                lo, tree_mask, **kwargs)


def _pool_scales(pool_k, scale_k, scale_v):
    """Resolve the per-page dequant scale operands: the caller's tensors
    for a quantized pool, all-ones for a float pool (exact multiply), so
    the kernels keep ONE pallas_call shape either way."""
    if scale_k is None:
        ones = jnp.ones((pool_k.shape[0], pool_k.shape[2]), jnp.float32)
        return ones, ones
    return scale_k, scale_v


def paged_tree_attention(q, pool_k, pool_v, k_new, v_new, block_table,
                         key_pos, pos, tree_depth, tree_mask, *,
                         scale_k=None, scale_v=None):
    """Paged-cache verification path (models/attention.py, paged engines).

    pool_k/pool_v are ONE layer's shared page pool ``(n_pages + 1, ps,
    Hkv, hd)`` (trash page last); block_table/key_pos/pos are the
    per-sequence rows.  ``scale_k/scale_v (n_pages + 1, Hkv)`` are the
    int8 pool's per-page dequant scales (None = float pool).  Windowed
    attention is dense-only (the ring IS the window), so there is no
    ``window`` here.
    """
    B = q.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_pos = pos_b[:, None] + tree_depth[None, :].astype(jnp.int32)  # (B, W)
    lo = jnp.full_like(q_pos, -1)
    sk, sv = _pool_scales(pool_k, scale_k, scale_v)
    return _tree.paged_tree_attention(q, pool_k, pool_v, sk, sv, k_new,
                                      v_new, block_table, key_pos, q_pos,
                                      lo, tree_mask, interpret=_interpret())


def paged_cache_attention(q, pool_k, pool_v, block_table, key_pos, pos,
                          tree_depth, *, scale_k=None, scale_v=None):
    """Cache-only half of the paged verify split (``tree_kernel=sparse``):
    the quantized page walk WITHOUT the tree block.  Returns ``(o, m, l)``
    merge partials; the caller merges them with the
    ``sparse_tree_attention_partial`` tree half."""
    B = q.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_pos = pos_b[:, None] + tree_depth[None, :].astype(jnp.int32)  # (B, W)
    lo = jnp.full_like(q_pos, -1)
    sk, sv = _pool_scales(pool_k, scale_k, scale_v)
    return _tree.paged_cache_attention(q, pool_k, pool_v, sk, sv,
                                       block_table, key_pos, q_pos, lo,
                                       interpret=_interpret())


def decode_attention(q, ck, cv, k_new, v_new, key_pos, pos, *, window=0):
    """Plain decode = W=1 tree."""
    return tree_attention(q, ck, cv, k_new, v_new, key_pos, pos,
                          jnp.zeros((1,), jnp.int32),
                          jnp.ones((1, 1), bool), window=window)


def sparse_tree_attention(q, k_new, v_new, tree_mask, *, backend="pallas"):
    """W×W tree-correlation attention (sparse part only).

    Dispatches per ``backend`` like ``attn_verify`` does — ``"ref"`` runs
    the jnp oracle, ``"pallas"`` the block-masked kernel.
    """
    if backend == "ref":
        from repro.kernels import ref as _ref
        return _ref.sparse_tree_ref(q, k_new, v_new, tree_mask)
    return _sparse.sparse_tree_attention(q, k_new, v_new, tree_mask,
                                         interpret=_interpret())


def sparse_tree_attention_partial(q, k_new, v_new, tree_mask):
    """Tree half of the split verify path: UNNORMALIZED ``(o, m, l)``
    merge partials of the W×W masked tree attention (merged with the
    ``paged_cache_attention`` page walk by the caller)."""
    return _sparse.sparse_tree_attention_partial(q, k_new, v_new, tree_mask,
                                                 interpret=_interpret())
