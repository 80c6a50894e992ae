"""Operations and bytes the algorithm needs, from shapes alone.

A roofline's least time is ``max(flops / peak_flops, bytes / peak_bw)``:
the work and traffic the algorithm cannot avoid, not what a kernel happens
to move.  Rows of a bank that are free or finished need nothing.
"""
from __future__ import annotations

import numpy as np


def tree_mask_nnz(width: int, ancestors: np.ndarray) -> int:
    """Query-key pairs inside the tree: ``ancestors`` is the (W, W)
    ancestor-or-self mask."""
    return int(np.asarray(ancestors, bool).sum())


def paged_tree_attention(dims: dict, width: int, mask_nnz: int, ctxs,
                         elem_bytes: int = 2):
    """(flops, bytes) of one call (one layer, one decode step) of the
    paged tree-verify attention for the live rows whose cached contexts
    are ``ctxs``: every tree query against every cached key plus its tree
    ancestors (QK and PV, 2 flops a multiply-add each), and the cached K
    and V read once, the queries, fresh tree K/V and outputs once."""
    hq, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    flops = bytes_ = 0
    for c in ctxs:
        flops += 4 * hd * hq * (width * int(c) + mask_nnz)
        bytes_ += elem_bytes * hd * (2 * hkv * int(c)        # cached K, V
                                     + 2 * width * hq         # q, out
                                     + 2 * width * hkv)       # tree K, V
    return flops, bytes_


def token_flops(dims: dict, ctx: int) -> int:
    """Forward flops of one token at cached context ``ctx``: 2 per weight
    it multiplies through (``dims["token_params"]``, counted by the
    architecture module), plus attention over ``ctx`` keys (QK and PV) in
    every layer."""
    attn = 4 * dims["head_dim"] * dims["n_heads"] * int(ctx) \
        * dims["n_layers"]
    return 2 * dims["token_params"] + attn
