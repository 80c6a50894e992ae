"""Greedy tree acceptance (predict-then-verify fallback to the longest
validated prefix) and the full Ghidorah speculative decoding step.

Acceptance walk (jit-friendly, fixed shapes): start at the root; at each
depth pick the child whose token equals the argmax of the current node's
logits; stop when none matches.  The last accepted node's argmax becomes the
*bonus* token — tokens emitted per step = (accepted chain - root) + 1 bonus
= the paper's acceptance length.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.speculative.medusa import draft_candidates, expand_tree_tokens


def accept_walk(tree, tree_tokens, logits):
    """tree_tokens: (B, W); logits: (B, W, V).

    Returns dict(n_accept (B,) total accepted incl. root, chain (B, Dmax)
    node ids padded with the last accepted node, bonus (B,) next token,
    last_node (B,)).
    """
    B, W, V = logits.shape
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, W)
    parent = tree.parent                                        # (W,)

    def body(d, state):
        cur, n_acc, alive, chain = state
        # child of `cur` whose token matches target[cur]
        tgt = jnp.take_along_axis(targets, cur[:, None], axis=1)[:, 0]  # (B,)
        is_child = parent[None, :] == cur[:, None]                      # (B,W)
        match = is_child & (tree_tokens == tgt[:, None]) & (tree.depth[None, :] == d)
        any_match = jnp.any(match, axis=1)
        nxt = jnp.argmax(match, axis=1).astype(jnp.int32)
        step_ok = alive & any_match
        cur = jnp.where(step_ok, nxt, cur)
        n_acc = n_acc + step_ok.astype(jnp.int32)
        chain = chain.at[:, d].set(jnp.where(step_ok, nxt, chain[:, d - 1]))
        return cur, n_acc, step_ok, chain

    cur0 = jnp.zeros((B,), jnp.int32)
    chain0 = jnp.zeros((B, tree.max_depth), jnp.int32)
    alive0 = jnp.ones((B,), bool)
    n0 = jnp.ones((B,), jnp.int32)                               # root counts
    cur, n_acc, _, chain = jax.lax.fori_loop(
        1, tree.max_depth, body, (cur0, n0, alive0, chain0))
    bonus = jnp.take_along_axis(targets, cur[:, None], axis=1)[:, 0]
    return {"n_accept": n_acc, "chain": chain, "bonus": bonus,
            "last_node": cur}


@partial(jax.tree_util.register_dataclass,
         data_fields=["cache", "cur_token", "hidden"], meta_fields=[])
@dataclasses.dataclass
class SpecState:
    """Carry between decode steps (any batch size B).

    Also the unified ``DecodeEngine`` state: a draft-free (sequential)
    strategy carries ``hidden=None`` — an empty pytree leaf — since there
    is no drafting input to thread."""
    cache: Any
    cur_token: jax.Array     # (B,) last committed token (next root)
    hidden: Any              # (B, d) hidden at that token (drafting
                             # input), or None for draft-free strategies


def spec_step(model, params, heads, tree, state: SpecState, *, backend="ref",
              tree_kernel="dense", active=None, return_counts=False):
    """One Ghidorah speculative decoding step, batched over sequences.

    Each sequence accepts its own chain length; the commit is a per-sequence
    masked ring write, so positions diverge across the batch.
    Returns (new_state, out_tokens (B, Dmax) emitted tokens padded with the
    bonus, n_out (B,) = acceptance length this step).

    ``active (B,) bool`` freezes the rows where it is False: their
    acceptance count is forced to 0 (nothing committed, ``pos`` does not
    advance) and their carry (``cur_token``/``hidden``) is left untouched.
    The chunk driver uses this to stop finished / capacity-exhausted / free
    slots from writing into their cache rows while the rest of the batch
    keeps decoding (runtime/scheduler.py evicts them at the chunk boundary).

    ``return_counts`` appends the verify's expert counts (int32 (3,):
    1 forward, picks, distinct experts touched summed over layers; None
    for a model without experts) as a fourth result.
    """
    cfg = model.cfg
    # named scopes group the step's device ops by phase in a profile
    with jax.named_scope("draft"):
        cands, _ = draft_candidates(cfg, heads, state.hidden,
                                    cfg.medusa_top_k)
        tree_tokens = expand_tree_tokens(tree, state.cur_token, cands)
    with jax.named_scope("verify"):
        logits, extras = model.verify(params, state.cache, tree_tokens,
                                      tree, backend=backend,
                                      tree_kernel=tree_kernel)
        acc = accept_walk(tree, tree_tokens, logits)

    with jax.named_scope("commit"):
        # batched commit: per-sequence accepted chain / length / path
        n_accept = acc["n_accept"]
        if active is not None:
            n_accept = jnp.where(active, n_accept, 0)
        path_idx = tree.node_path[acc["last_node"]]          # (B,)
        cache = model.commit(state.cache, extras, tree, acc["chain"],
                             n_accept, path_idx)

        hidden = extras["hidden"]                   # (B, W, d)
        new_hidden = jnp.take_along_axis(
            hidden, acc["last_node"][:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        cur_token = acc["bonus"]
        if active is not None:
            cur_token = jnp.where(active, cur_token, state.cur_token)
            new_hidden = jnp.where(active[:, None], new_hidden,
                                   state.hidden)
        new_state = SpecState(cache=cache, cur_token=cur_token,
                              hidden=new_hidden)

        # emitted tokens: accepted children (chain[1:n]) then the bonus
        # token.  position j < n-1 emits tree_tokens[chain[j+1]]; position
        # n-1 emits bonus.
        idx = jnp.arange(tree.max_depth)[None, :]
        chain_tokens = jnp.take_along_axis(tree_tokens, acc["chain"],
                                           axis=1)
        child_shift = jnp.concatenate(
            [chain_tokens[:, 1:], chain_tokens[:, -1:]], axis=1)
        emitted = jnp.where(idx < (acc["n_accept"] - 1)[:, None],
                            child_shift, 0)
        emitted = jnp.where(idx == (acc["n_accept"] - 1)[:, None],
                            acc["bonus"][:, None], emitted)
    if return_counts:
        return new_state, emitted, n_accept, extras.get("moe")
    return new_state, emitted, n_accept


def spec_prefill(model, params, heads, batch, *, max_len, window=0):
    """Prefill + initial draft state."""
    logits, extras, cache = model.prefill(batch=batch, params=params,
                                          max_len=max_len, window=window)
    last = logits[:, -1]
    cur = jnp.argmax(last, axis=-1).astype(jnp.int32)
    hidden = extras["hidden"][:, -1]
    return SpecState(cache=cache, cur_token=cur, hidden=hidden)
