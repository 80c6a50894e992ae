"""Runtime HCMP: the draft/verify executor split (paper §III-B at runtime).

``core/hcmp/sharding.py`` is the lowering study — how HCMP partitions a
single forward across a mesh.  This module is HCMP as the *serving
runtime* sees it: the ``DecodeStrategy``'s two compute phases live on
separate executors and the step pipeline overlaps them.

Executor split (Dovetail's affinity argument):

  * **VerifyExecutor** (device 0) — the full-model tree forward
    (``model.verify`` + ``accept_walk``) and the KV commit.  Weight- and
    bandwidth-heavy; owns the KV cache.
  * **DraftExecutor** (device 1) — the Medusa heads
    (``draft_candidates`` + ``expand_tree_tokens``).  A few small
    matmuls over one hidden vector per row; owns a private copy of the
    heads, placed once at construction.

Pipeline (PEARL-style overlap, adapted to Medusa's self-drafting):
Medusa drafts from the VERIFIER's hidden state, so draft(t+1) cannot
start before verify(t)'s forward finishes — the true overlap window is
the verifier's *commit*: step t's KV commit (device 0) runs concurrently
with drafting step t+1 (device 1), and across chunk boundaries the next
chunk's first draft is computed ahead of time ("pre-draft") while the
host does its boundary bookkeeping.  A pre-draft is tagged with the
engine's bank epoch + strategy shape; any bank mutation between chunks
(admission, reset, strategy switch) bumps the epoch, the stale pre-draft
is DISCARDED and redrafted from the committed state.  Greedy tree
verification commits the greedy chain whatever the draft proposes, so a
discarded-vs-reused pre-draft can never change emitted tokens: the
overlap engine is bit-identical to the inline ``chunk_scan`` driver.

Ownership rules (single-threaded host, two async device streams —
documented here and in ``src/repro/analysis/README.md``; there are no
host locks, so reprolint's R4 has nothing to guard):

  * device 0 owns ``state.cache`` — only ``verify_front`` reads it and
    only ``commit_step`` (donated) writes it, both on device 0's FIFO
    stream, so read-before-donate is ordered by the stream itself;
  * device 1 owns the runner's heads copy — placed once, never written;
  * the host runner owns the pre-draft slot and the hit/discard
    counters — it is only ever entered from the engine's single-threaded
    ``sched_step``/``generate`` callers.

Placement (``executor_pair``): on the CPU backend the two executors are
two XLA host devices requested with
``--xla_force_host_platform_device_count=2`` (``ensure_host_devices``),
so the overlap is real there.  On a TPU host both executors are the first
chip: a second chip is never taken without being asked, so one chip runs
draft and verify serially on its own stream — still bit-identical to the
inline scan, with no overlap.  Whether the draft should move to the host
CPU or to another chip is for ARCA to measure, not for this module to
assume.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.speculative.medusa import draft_candidates, expand_tree_tokens
from repro.core.speculative.verify import SpecState, accept_walk
from repro.runtime.cache import capacity_left

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _cpu_platform() -> bool:
    """Whether JAX is pinned to the CPU platform, read from the platform
    setting alone (asking the backend would initialize it, after which
    the host device count can no longer change)."""
    platforms = jax.config.jax_platforms or os.environ.get(
        "JAX_PLATFORMS", "")
    return platforms.split(",")[0].strip() == "cpu"


def ensure_host_devices(n: int = 2) -> int:
    """Best-effort request for ``n`` XLA host CPU devices when JAX runs on
    the CPU platform; on any other platform it adds nothing.

    Only effective BEFORE the jax backend initializes (serve.py calls it
    before building the model); afterwards it is a no-op probe.  Returns
    the number of devices actually visible — callers must tolerate 1
    (the runner then runs both executors on device 0, serially)."""
    if _cpu_platform() and _DEVICE_FLAG not in os.environ.get("XLA_FLAGS",
                                                               ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" {_DEVICE_FLAG}={n}").strip()
    return len(jax.devices())


def executor_pair():
    """(verify_device, draft_device): the first two host devices on the
    CPU backend; device 0 twice otherwise (one device, or accelerators,
    where a second chip is never taken without being asked)."""
    devs = jax.devices()
    if jax.default_backend() == "cpu" and len(devs) > 1:
        return devs[0], devs[1]
    return devs[0], devs[0]


class HcmpOverlapRunner:
    """Disaggregated chunk driver: same signature and bit-identical
    outputs as the engine's inline ``chunk_scan``, with the step split
    across the two executors.

    Per step: ``verify_front`` (device 0) runs the tree forward, the
    acceptance walk and the whole emission/EOS/budget fold of the inline
    scan body; the accepted-chain operands then fan out — ``draft_step``
    for t+1 is dispatched to device 1 *before* ``commit_step`` is
    dispatched to device 0, so XLA's async streams execute the draft
    concurrently with the commit.  The final iteration's draft becomes
    the next chunk's pre-draft."""

    def __init__(self, model, heads, *, backend: str = "ref",
                 tree_kernel: str = "dense"):
        self.verify_dev, self.draft_dev = executor_pair()
        # DraftExecutor owns its heads copy: placed once, read-only
        self.heads = jax.device_put(heads, self.draft_dev)
        cfg = model.cfg

        # NAMED jit targets (not lambdas): the tracecount audit buckets
        # compile counts per __name__ against compile_budget.json
        def draft_step(h, strat, cur, hidden):
            cands, _ = draft_candidates(cfg, h, hidden, cfg.medusa_top_k)
            return expand_tree_tokens(strat.tree, cur, cands)

        def verify_front(p, strat, cache, cur, hidden, tree_tokens, done,
                         rem, eos):
            # identical semantics to the inline chunk_scan body with
            # spec_step split open (verify/accept here, commit deferred)
            done = done | (rem <= 0) | \
                (capacity_left(cache) < strat.tree.max_depth)
            active = ~done
            tree = strat.tree
            logits, extras = model.verify(p, cache, tree_tokens, tree,
                                          backend=backend,
                                          tree_kernel=tree_kernel)
            acc = accept_walk(tree, tree_tokens, logits)
            n_accept = jnp.where(active, acc["n_accept"], 0)
            path_idx = tree.node_path[acc["last_node"]]
            new_hidden = jnp.take_along_axis(
                extras["hidden"],
                acc["last_node"][:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            cur_token = jnp.where(active, acc["bonus"], cur)
            new_hidden = jnp.where(active[:, None], new_hidden, hidden)
            # emission: accepted children then the bonus (spec_step), then
            # the chunk driver's EOS truncation + budget fold
            idx = jnp.arange(tree.max_depth)[None, :]
            chain_tokens = jnp.take_along_axis(tree_tokens, acc["chain"],
                                               axis=1)
            child_shift = jnp.concatenate(
                [chain_tokens[:, 1:], chain_tokens[:, -1:]], axis=1)
            emitted = jnp.where(idx < (acc["n_accept"] - 1)[:, None],
                                child_shift, 0)
            emitted = jnp.where(idx == (acc["n_accept"] - 1)[:, None],
                                acc["bonus"][:, None], emitted)
            valid = idx < n_accept[:, None]
            is_eos = valid & (emitted == eos)
            has_eos = jnp.any(is_eos, axis=1)
            n_cut = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1,
                              n_accept)
            n_eff = jnp.where(active, n_cut, 0)
            emitted = jnp.where(idx < n_eff[:, None], emitted, eos)
            done = done | has_eos
            rem = rem - n_eff
            return (done, rem, cur_token, new_hidden, emitted, n_eff,
                    acc["chain"], n_accept, path_idx, extras)

        def commit_step(cache, extras, strat, chain, n_accept, path_idx):
            return model.commit(cache, extras, strat.tree, chain, n_accept,
                                path_idx)

        self._draft = jax.jit(draft_step)
        # the cache is NOT donated here: commit_step (below) is the sole
        # writer and donates it; verify_front's read strictly precedes
        # that commit on device 0's FIFO stream
        # reprolint: disable=R2 (read-only cache; commit_step donates it)
        self._verify = jax.jit(verify_front)
        self._commit = jax.jit(commit_step, donate_argnums=(0,))

        # pre-draft slot: (epoch, strategy shape, batch) -> tree_tokens
        self._predraft: Optional[tuple] = None
        self.chunks = 0
        self.steps = 0
        self.predraft_hits = 0
        self.predraft_discards = 0

    # ---- pre-draft lifecycle ---------------------------------------------
    def _take_predraft(self, epoch, strategy, B):
        """Consume the stored pre-draft if it matches the bank's current
        epoch/strategy/width; count a hit or a mis-speculation discard."""
        slot, self._predraft = self._predraft, None
        if slot is None:
            return None
        tag_epoch, tag_shape, tag_b, tokens = slot
        if tag_epoch == epoch and tag_shape == strategy.shape() \
                and tag_b == B:
            self.predraft_hits += 1
            return tokens
        self.predraft_discards += 1
        return None

    def run_chunk(self, params, strategy, state, done, rem, K, eos, epoch):
        """K overlapped steps; returns ``(state, done, rem, toks (K, B,
        Dmax), ns (K, B))`` — the inline ``chunk_scan`` signature.  Pure
        async dispatch: no host sync in this loop (the caller's boundary
        sync materializes the outputs, same budget as inline)."""
        assert strategy.draft == "medusa", "overlap needs a drafted strategy"
        B = int(state.cur_token.shape[0])
        cache, cur, hidden = state.cache, state.cur_token, state.hidden
        tree_tokens = self._take_predraft(epoch, strategy, B)
        strat_d = jax.device_put(strategy, self.draft_dev)
        if tree_tokens is None:
            tree_tokens = self._draft(
                self.heads, strat_d,
                jax.device_put(cur, self.draft_dev),
                jax.device_put(hidden, self.draft_dev))
        toks, ns = [], []
        for _ in range(K):
            (done, rem, cur, hidden, emitted, n_eff, chain, n_accept,
             path_idx, extras) = self._verify(
                params, strategy, cache,
                cur, hidden, jax.device_put(tree_tokens, self.verify_dev),
                done, rem, eos)
            # dispatch the NEXT draft to device 1 BEFORE the commit to
            # device 0: the transfer waits on verify(t), then draft(t+1)
            # executes concurrently with commit(t) — the overlap window
            tree_tokens = self._draft(
                self.heads, strat_d,
                jax.device_put(cur, self.draft_dev),
                jax.device_put(hidden, self.draft_dev))
            cache = self._commit(cache, extras, strategy, chain, n_accept,
                                 path_idx)
            toks.append(emitted)
            ns.append(n_eff)
            self.steps += 1
        # the dangling draft is next chunk's pre-draft (valid while the
        # bank is untouched between chunks; any mutation bumps the epoch)
        self._predraft = (epoch, strategy.shape(), B, tree_tokens)
        self.chunks += 1
        state = SpecState(cache=cache, cur_token=cur, hidden=hidden)
        return state, done, rem, jnp.stack(toks), jnp.stack(ns)

    @property
    def stats(self) -> dict:
        return {
            "verify_device": str(self.verify_dev),
            "draft_device": str(self.draft_dev),
            "devices": len(jax.devices()),
            "chunks": self.chunks,
            "steps": self.steps,
            "predraft_hits": self.predraft_hits,
            "predraft_discards": self.predraft_discards,
        }
