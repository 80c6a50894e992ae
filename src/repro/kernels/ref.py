"""Pure-jnp oracles for the Pallas kernels (the allclose targets).

These mirror the models/attention.py reference math but with the exact
argument layout the kernels take, so tests can sweep shapes/dtypes and
assert kernel == oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import common as cm


def tree_attention_ref(q, ck, cv, k_new, v_new, key_pos, q_pos, lo,
                       tree_mask):
    """Fused dense(cache)+sparse(tree) verification attention.

    q:        (B, W, Hq, hd)
    ck, cv:   (B, S, Hkv, hd)   KV cache
    k_new:    (B, W, Hkv, hd)   fresh tree KVs
    key_pos:  (B, S) int32      absolute position per cache slot (-1 empty)
    q_pos:    (B, W) int32      absolute position per query node
    lo:       (B, W) int32      window lower bound per query (-1 = no window)
    tree_mask:(W, W) bool       ancestor-or-self
    returns   (B, W, Hq, hd) in q.dtype

    1-D ``key_pos``/``q_pos``/``lo`` (shared across the batch) are broadcast.
    """
    B, W = q.shape[:2]
    key_pos = jnp.broadcast_to(key_pos, (B, ck.shape[1]))
    q_pos = jnp.broadcast_to(q_pos, (B, W))
    lo = jnp.broadcast_to(lo, (B, W))
    scale = q.shape[-1] ** -0.5
    cache_ok = ((key_pos[:, None, :] >= 0)
                & (key_pos[:, None, :] <= q_pos[:, :, None])
                & (key_pos[:, None, :] > lo[:, :, None]))      # (B, W, S)
    dense = cm.gqa_attend_partial(q, ck, cv, cache_ok[:, None], scale)
    sparse = cm.gqa_attend_partial(q, k_new, v_new,
                                   tree_mask[None, None], scale)
    return cm.merge_partials([dense, sparse]).astype(q.dtype)


def paged_tree_attention_ref(q, pool_k, pool_v, scale_k, scale_v, k_new,
                             v_new, block_table, key_pos, q_pos, lo,
                             tree_mask):
    """Paged oracle: gather each sequence's pages into the logical
    (B, S_logical, Hkv, hd) view (dequantizing through the per-page scales
    — all-ones for float pools), then run the dense oracle.

    pool_k/pool_v: (n_pages + 1, ps, Hkv, hd) ONE layer's pool (trash page
    last); scale_k/scale_v: (n_pages + 1, Hkv) per-page dequant scales (or
    None for a verbatim float gather); block_table: (B, max_pages) with -1
    = unreserved (reads the trash page; those slots carry key_pos == -1 so
    every mask rejects them); key_pos: (B, max_pages * ps).
    """
    from repro.runtime.cache import gather_pages_dequant
    ck = gather_pages_dequant(pool_k, scale_k, block_table)
    cv = gather_pages_dequant(pool_v, scale_v, block_table)
    return tree_attention_ref(q, ck, cv, k_new, v_new, key_pos, q_pos, lo,
                              tree_mask)


def paged_cache_attention_ref(q, pool_k, pool_v, scale_k, scale_v,
                              block_table, key_pos, q_pos, lo):
    """Cache-only-half oracle: the paged gather + dense partial, returning
    the same ``(o, m, l)`` merge partials as the kernel wrapper."""
    from repro.runtime.cache import gather_pages_dequant
    ck = gather_pages_dequant(pool_k, scale_k, block_table)
    cv = gather_pages_dequant(pool_v, scale_v, block_table)
    B, W = q.shape[:2]
    key_pos = jnp.broadcast_to(key_pos, (B, ck.shape[1]))
    q_pos = jnp.broadcast_to(q_pos, (B, W))
    lo = jnp.broadcast_to(lo, (B, W))
    scale = q.shape[-1] ** -0.5
    cache_ok = ((key_pos[:, None, :] >= 0)
                & (key_pos[:, None, :] <= q_pos[:, :, None])
                & (key_pos[:, None, :] > lo[:, :, None]))       # (B, W, S)
    return cm.gqa_attend_partial(q, ck, cv, cache_ok[:, None], scale)


def decode_attention_ref(q, ck, cv, k_new, v_new, key_pos, q_pos, lo):
    """W=1 special case (plain decode)."""
    W = q.shape[1]
    assert W == 1
    return tree_attention_ref(q, ck, cv, k_new, v_new, key_pos, q_pos, lo,
                              jnp.ones((1, 1), bool))


def sparse_tree_ref(q, k_new, v_new, tree_mask):
    """Sparse-part-only oracle (paper Fig. 10b comparisons): masked softmax
    attention among the W tree tokens.  Returns normalized output."""
    scale = q.shape[-1] ** -0.5
    return cm.gqa_attend(q, k_new, v_new, tree_mask[None, None], scale)


def sparse_tree_attention_partial_ref(q, k_new, v_new, tree_mask):
    """Tree-half oracle for the split verify path: UNNORMALIZED ``(o, m,
    l)`` merge partials of the W×W masked tree attention."""
    scale = q.shape[-1] ** -0.5
    return cm.gqa_attend_partial(q, k_new, v_new, tree_mask[None, None],
                                 scale)


def grouped_matmul_ref(lhs, rhs, group_sizes):
    """``lhs (m, k)`` rows sorted by group times their group's ``rhs (G,
    k, n)``, float32 accumulation, in ``lhs``'s dtype (``lax.ragged_dot``:
    rows past ``sum(group_sizes)`` come out zero)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
