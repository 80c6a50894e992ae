"""Unified serving engine: ONE device-resident chunked decode driver
parameterized by a pluggable ``DecodeStrategy``.

A strategy is a registered pytree bundling the *verification tree* (the
PR 1 ``Tree`` machinery), its *width*, and the *draft source*:

  * ``DecodeStrategy.medusa(tree_spec)`` — Ghidorah speculative decoding:
    Medusa heads draft, the tree is verified in one forward, each sequence
    accepts its own chain (paper §III).
  * ``DecodeStrategy.sequential()`` — the degenerate ``chain_spec(width=1)``
    strategy: the tree is just the root (the last committed token), there
    is no draft source, and "verifying" the root alone IS plain one-token
    decoding — so the engine runs ``model.decode`` for it and the classic
    sequential baseline falls out of the same driver, protocol and slot
    lifecycle as speculation instead of a copy-pasted twin engine.

``BatchEngine`` and ``SpeculativeEngine`` survive as thin constructor
aliases over ``DecodeEngine`` (bit-identical outputs to the pre-unification
engines); everything below them — the K-step ``lax.scan`` chunk driver, the
``sched_*`` continuous-batching slot protocol, admission/insert/reset and
the paged-pool bookkeeping — is ONE implementation.

Because the strategy is a jit ARGUMENT of the chunk functions, it can be
swapped at runtime between chunks (``set_strategy``): same-shape strategies
(equal ``(draft, width, max_depth, n_paths)``) reuse the compiled scans, so
the scheduler's adaptive mode (runtime/scheduler.py) re-decides the
speculative width from *measured* acceptance/step-time without re-jitting,
and ARCA's measured time source (core/arca.py ``profile_engine`` ->
``time_step``) times exactly the deployed step function.

Chunked driver semantics (unchanged from the split engines): K steps run
inside a single jitted ``lax.scan`` with ONE host sync per chunk.  A row
goes (and stays) done on EOS, on its ``rem`` budget reaching 0, or on a
capacity freeze — a full (window=0) KV cache that cannot take a worst-case
accepted chain (``capacity_left < tree.max_depth``; depth 1 for the
sequential strategy) freezes instead of silently wrapping its ring.  Done
speculative rows commit nothing (``spec_step(active=...)``); done
sequential rows keep stepping with emission masked and their KV
bookkeeping (``key_pos``/``pos``) frozen, so mid-chunked-prefill rows keep
their piece offsets.  The host loop clamps the chunk length to the largest
remaining budget (power-of-two schedule, bounded compile cache).

Slot lifecycle (continuous batching, runtime/scheduler.py): each batch row
is a *slot*; admission/eviction happen only between chunks via the
``sched_*`` protocol, so the compiled scans are reused across the whole
request stream.  Paged KV (``paged=True``): the bank's KV lives in one
shared page pool (runtime/cache.py) with host-side page reservations at
admission and a trash-page redirect for overflow writes; with runtime
strategy switching the reservation overshoot is the DEEPEST registered
candidate tree (``register_strategies``), so a mid-request switch can
never outgrow a row's reservation.

All state-threading jits (chunk scans, ``sched_admit``, ``sched_insert``,
``sched_reset``) DONATE the carried state, so the cache — one large pool
when paged — is updated in place instead of copied every chunk.

HCMP executor split (``hcmp="overlap"``, core/hcmp/executors.py): the
drafted strategy's two phases run on separate executors — Medusa heads
(DraftExecutor, device 1) and the full-model tree verify + commit
(VerifyExecutor, device 0) — pipelined so drafting step t+1 overlaps
step t's KV commit, with a cross-chunk pre-draft versioned by the bank
epoch (any ``sched_*`` mutation or strategy switch bumps it; a stale
pre-draft is discarded and redrafted).  The routing happens inside
``_run_chunk`` below the ``sched_*`` protocol, so the scheduler is
unchanged and outputs stay bit-identical to the inline scan.  ARCA
times both partitions (``time_step(..., hcmp=...)`` ->
``profile_engine``) and ``Strategy.hcmp`` records the measured choice.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.speculative.tree import Tree, TreeSpec, chain_spec
from repro.core.speculative.verify import SpecState, spec_step
from repro.runtime.cache import (PageAllocator, blank_paged_rows,
                                 capacity_left, insert_rows, pages_for,
                                 paginate_cache, reset_rows, slice_row,
                                 tile_rows, write_row_at)
from repro.runtime.sampling import greedy
from repro.runtime.telemetry import ExpertCounters

_NO_EOS = -1          # sentinel: no real token id is negative

_KV_DTYPES = {"fp32": jnp.float32, "f32": jnp.float32,
              "float32": jnp.float32, "bf16": jnp.bfloat16,
              "bfloat16": jnp.bfloat16, "int8": jnp.int8}


def _kv_dtype(kv_dtype):
    """Normalize the engine's ``kv_dtype`` knob: None keeps the model
    dtype; a name ("fp32" | "bf16" | "int8") or any jnp dtype picks the
    paged pool's storage dtype (int8 = quantized pages, runtime/cache.py)."""
    if kv_dtype is None:
        return None
    if isinstance(kv_dtype, str):
        if kv_dtype not in _KV_DTYPES:
            raise ValueError(f"kv_dtype must be one of "
                             f"{sorted(_KV_DTYPES)} or a dtype, "
                             f"got {kv_dtype!r}")
        return _KV_DTYPES[kv_dtype]
    return jnp.dtype(kv_dtype)


def _attn_backend(backend: Optional[str]) -> str:
    """The attention backend an engine serves with: the caller's choice,
    else the Pallas kernels on a TPU and the jnp reference elsewhere (the
    CPU runs Pallas only in its interpreter, a test tool)."""
    if backend is not None:
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _eos_scalar(eos) -> jnp.ndarray:
    return jnp.asarray(_NO_EOS if eos is None else int(eos), jnp.int32)


def _budget(n_tokens, batch) -> np.ndarray:
    """Per-sequence token budgets: scalar broadcast or (B,) array."""
    b = np.broadcast_to(np.asarray(n_tokens, np.int32), (batch,)).copy()
    if np.any(b < 1):
        raise ValueError("n_tokens must be >= 1 per sequence")
    return b


def _pow2_chunk(k_max: int, need: int) -> int:
    """Smallest power-of-two chunk covering ``need`` steps, capped at
    ``k_max``: bounds the tail-chunk overshoot AND the set of compiled scan
    lengths to {1, 2, 4, ..., k_max}."""
    k = 1
    while k < need and k < k_max:
        k *= 2
    return min(k, k_max)


def _prompt_len(batch) -> int:
    """Decoder-sequence length of a prefill batch: tokens plus any VLM
    patch embeds that join the decoder sequence (encoder frames do not)."""
    n = int(batch["tokens"].shape[1])
    if "patch_embeds" in batch:
        n += int(batch["patch_embeds"].shape[1])
    return n


# ===========================================================================
# DecodeStrategy: the runtime-swappable (tree, width, draft-source) bundle
# ===========================================================================
@partial(jax.tree_util.register_dataclass,
         data_fields=["tree"], meta_fields=["width", "draft"])
@dataclasses.dataclass(frozen=True)
class DecodeStrategy:
    """What one decode step does: verification tree + width + draft source.

    A registered pytree, passed as a jit ARGUMENT to the engine's chunk
    scans — strategies with equal ``shape()`` share one compiled scan, so
    swapping same-shape-bucketed strategies at a chunk boundary is pure
    data movement (no re-jit).  ``draft`` is static metadata:

      * ``"medusa"`` — Medusa heads draft candidates, the tree is verified
        in one forward (requires an engine constructed with ``heads``);
      * ``"none"`` — no draft source; the tree must be the degenerate
        ``chain_spec(1)`` root and the step is plain one-token decode.
    """
    width: int
    draft: str                   # "medusa" | "none"
    tree: Tree

    @property
    def max_depth(self) -> int:
        return self.tree.max_depth

    def shape(self) -> tuple:
        """Compile-cache bucket: strategies with equal shape reuse the
        engine's compiled chunk scans."""
        return (self.draft,) + self.tree.shape()

    @staticmethod
    def sequential() -> "DecodeStrategy":
        """The degenerate width-1 strategy: tree = chain_spec(1) (root
        only), no draft — sequential decoding."""
        return DecodeStrategy(width=1, draft="none",
                              tree=Tree.from_spec(chain_spec(1)))

    @staticmethod
    def medusa(spec: TreeSpec) -> "DecodeStrategy":
        return DecodeStrategy(width=spec.width, draft="medusa",
                              tree=Tree.from_spec(spec))


# ===========================================================================
# unified engine state + row surgery (ONE implementation for both drafts)
# ===========================================================================
# The engine state is core/speculative/verify.py ``SpecState``; the
# sequential strategy carries ``hidden=None`` (an empty pytree leaf), so
# every insert/reset/admit path below handles both drafts with one body.

def _prefill_state(model, params, heads, batch, *, max_len, window):
    """Prefill -> (engine state, expert counts of a sparse-expert model or
    None).  ``heads is None`` selects the draft-free path (no hidden
    carry)."""
    logits, extras, cache = model.prefill(params, batch, max_len=max_len,
                                          window=window)
    hidden = None if heads is None else extras["hidden"][:, -1]
    return (SpecState(cache=cache, cur_token=greedy(logits[:, -1]),
                      hidden=hidden), extras.get("moe"))


def _insert_row(state, b, row, pages=None):
    cache = insert_rows(state.cache, b, row.cache) if pages is None else \
        insert_rows(state.cache, b, row.cache, pages=pages)
    hid = None if state.hidden is None else \
        state.hidden.at[b].set(row.hidden[0])
    return type(state)(cache=cache,
                       cur_token=state.cur_token.at[b].set(row.cur_token[0]),
                       hidden=hid)


def _admit_row(model, params, heads, state, b, batch, *, max_len, window):
    row, counts = _prefill_state(model, params, heads, batch,
                                 max_len=max_len, window=window)
    return _insert_row(state, b, row), row.cur_token[0], counts


def _admit_row_paged(model, params, heads, state, b, batch, pages):
    row, counts = _prefill_state(model, params, heads, batch, max_len=1,
                                 window=0)
    return _insert_row(state, b, row, pages=pages), row.cur_token[0], counts


def _reset_state_rows(state, mask):
    # a freed slot must be fully inert, carry included: ``cur_token`` seeds
    # the next chunk's decode input and ``hidden`` keeps driving (masked)
    # drafts, so a stale carry is one masking bug away from leaking into a
    # recycled page.  Clear the whole row.
    mask = jnp.asarray(mask)
    hid = None if state.hidden is None else \
        jnp.where(mask[:, None], jnp.zeros_like(state.hidden), state.hidden)
    return type(state)(cache=reset_rows(state.cache, mask),
                       cur_token=jnp.where(mask,
                                           jnp.zeros_like(state.cur_token),
                                           state.cur_token),
                       hidden=hid)


def _extend_row(model, params, state, b, tokens, n_valid, tree):
    """Chunked-prefill piece: run ``tokens (1, C)`` through the causal
    verify path (``tree`` = chain spec — plain causal attention at the
    row's offset, ref numerics) against row ``b``'s cache view and splice
    the piece's KVs in.  The drafting carry (``cur_token``/``hidden`` when
    present) tracks the last REAL position, so the final piece leaves the
    row exactly as a whole-prompt admission would.  Returns (state, last
    token, expert counts or None)."""
    row_view = slice_row(state.cache, b)
    logits, extras = model.verify(params, row_view, tokens, tree,
                                  backend="ref")
    k1, v1 = extras["tree_kv"]                       # (L, 1, C, Hkv, hd)
    cache = write_row_at(state.cache, b, k1[:, 0], v1[:, 0],
                         row_view.kv.pos[0], n_valid)
    last = greedy(jnp.take(logits[0], n_valid - 1, axis=0))
    hid = None if state.hidden is None else state.hidden.at[b].set(
        jnp.take(extras["hidden"][0], n_valid - 1, axis=0))
    return type(state)(cache=cache,
                       cur_token=state.cur_token.at[b].set(last),
                       hidden=hid), last, extras.get("moe")


def _seq_step(model, params, state, *, backend, active):
    """One step of the degenerate ``chain_spec(width=1)`` strategy: the
    tree is just the root (the last committed token) and there is no draft,
    so verifying it IS plain one-token decode.  Interface mirrors
    ``spec_step``: returns (state, emitted (B, 1), n (B,) in {0, 1}).

    Every row decodes, done ones included — their ``key_pos``/``pos`` are
    restored afterwards so a done row's KV bookkeeping is frozen (its
    garbage k/v write stays invisible at key_pos -1 and is overwritten by
    the slot's next real write).  Without this a mid-chunked-prefill row
    (done-masked while its prompt pieces land) would have its piece offsets
    corrupted between pieces."""
    kv0 = state.cache.kv
    lg, cache = model.decode(params, state.cache, state.cur_token[:, None],
                             backend=backend)
    if kv0 is not None:
        done = ~active
        kv = cache.kv
        cache = dataclasses.replace(
            cache, kv=dataclasses.replace(
                kv,
                key_pos=jnp.where(done[:, None], kv0.key_pos, kv.key_pos),
                pos=jnp.where(done, kv0.pos, kv.pos)))
    nxt = greedy(lg[:, 0])
    cur = jnp.where(active, nxt, state.cur_token)
    return (type(state)(cache=cache, cur_token=cur, hidden=state.hidden),
            nxt[:, None], active.astype(jnp.int32))


@runtime_checkable
class SchedulableEngine(Protocol):
    """The slot protocol ``runtime/scheduler.py`` drives engines through.

    This is the declared source of truth for the scheduler/engine
    contract; reprolint's R6 cross-checks it against the scheduler's
    actual ``sched_*`` call sites, so it can never silently lag them.
    Every method below is REQUIRED (called unconditionally at chunk
    boundaries) except the last three, which the scheduler/server probe
    with ``getattr``/``hasattr``.  Two optional *properties* are part of
    the wider contract but kept out of this Protocol so it stays
    ``issubclass``-checkable (runtime_checkable Protocols with non-method
    members reject issubclass): ``sched_chunked_ok`` (chunked-prefill
    support) and ``sched_pages_held`` (pages reserved by resident rows).

    Slot-state conventions: ``state`` is the opaque resident-bank carry
    (a registered pytree, donated by every state-threading jit), ``row``
    an opaque B=1 prefill result, ``b`` a bank slot index.
    """

    # ---- admission sizing (host-side, no device work) --------------------
    def sched_footprint(self, prompt_len: int, n_tokens: int) -> int: ...
    def sched_can_admit(self, prompt_len: int, n_tokens: int) -> bool: ...

    # ---- row lifecycle ---------------------------------------------------
    def sched_prefill(self, batch): ...
    def sched_first(self, row) -> int: ...
    def sched_blank(self, row, batch): ...
    def sched_insert(self, state, b, row, *, prompt_len=None,
                     n_tokens=None): ...
    def sched_admit(self, state, b, batch, *, n_tokens=None,
                    reserve_len=None): ...
    def sched_extend(self, state, b, tokens, n_valid): ...
    def sched_reset(self, state, b): ...
    def sched_release(self, b: int) -> None: ...

    # ---- the chunk step --------------------------------------------------
    def sched_step(self, state, done, rem, K, eos_val): ...
    def sched_fetch(self, raw): ...
    def sched_emitted(self, raw): ...

    # ---- optional extensions (probed with getattr/hasattr) ---------------
    def sched_abort(self, b: int) -> None: ...
    def sched_pool_conserved(self) -> bool: ...
    def sched_drained(self) -> bool: ...


class _PagedPoolMixin:
    """Shared page-reservation bookkeeping for paged engines.

    The allocator is HOST state: pages move between the free list and rows
    only at admission/eviction boundaries (and once per ``generate``), so
    reservation never syncs the device.  ``_overshoot`` is the engine's
    worst-case slots written past the budget: one full accepted chain of
    the current strategy's ``max_depth`` (1 for sequential — decode writes
    one slot past the last emitted token), ratcheted to the deepest
    registered candidate when runtime switching is armed."""

    def _paged_init(self, *, paged, page_size, pool_pages):
        if paged and self.window:
            raise ValueError("paged KV takes no engine-wide window (a "
                             "windowed stack serves from the dense ring, "
                             "which IS the window); per-layer windows "
                             "(ModelConfig.layer_types) are masked in the "
                             "paged path itself")
        self.paged, self.page_size = paged, page_size
        self.pool_pages = pool_pages
        self.max_pages = pages_for(self.max_len, page_size) if paged else 0
        self._alloc: Optional[PageAllocator] = None      # sched-bank state
        self._row_pages = {}
        self._extends = {}          # piece width -> jitted prefill-extend

    def _need_pages(self, prompt_len: int, budget: int, n_total: int) -> int:
        return min(pages_for(prompt_len + budget + self._overshoot,
                             self.page_size),
                   self.max_pages, n_total)

    def _reserve_tables(self, batch, budget):
        """Per-row page reservations for a ``generate`` call.  When the
        pool cannot cover a row's need the reservation is PARTIAL — the row
        then freezes at ``capacity_left`` with its shortfall reported in
        ``n_emitted``, it never borrows a neighbor's pages."""
        B = int(batch["tokens"].shape[0])
        n_total = self.pool_pages or B * self.max_pages
        alloc = PageAllocator(n_total)
        prompt = _prompt_len(batch)
        tables = np.full((B, self.max_pages), -1, np.int32)
        for b in range(B):
            pages = alloc.alloc_upto(
                self._need_pages(prompt, int(budget[b]), n_total))
            tables[b, :len(pages)] = pages
        return jnp.asarray(tables), n_total

    # ---- scheduler-facing reservation hooks ------------------------------
    def sched_footprint(self, prompt_len: int, n_tokens: int) -> int:
        """Slot cost of a request — what the scheduler's size-ordered
        admission policies (SJF/LPT) rank by: reserved pages when paged,
        otherwise logical slots (prompt + budget + overshoot)."""
        need = int(prompt_len) + int(n_tokens) + self._overshoot
        if self.paged:
            return pages_for(need, self.page_size)
        return need

    @property
    def sched_chunked_ok(self) -> bool:
        """Whether this engine supports chunked prefill (piecewise
        ``sched_extend`` admission): attention-only families without an
        engine-wide window.  Recurrent families (Mamba/xLSTM/hybrid)
        prefill their state sequentially and stay on whole-prompt
        admission; an engine-wide window keeps its ring (a piece would
        have to wrap it) and so whole-prompt admission too.  Per-layer
        windows (``layer_types``) mask each piece like any query and keep
        chunked prefill on."""
        return self.window == 0 and \
            getattr(self.model, "family", "") in ("dense", "moe", "vlm")

    def sched_can_admit(self, prompt_len: int, n_tokens: int) -> bool:
        """False while the pool cannot fund the request's reservation — the
        scheduler then DEFERS admission until evictions free pages.  A
        request bigger than the whole pool caps at the pool (admitted once
        fully free; it freezes with a shortfall, it is not rejected)."""
        if not self.paged or self._alloc is None:
            return True
        return self._alloc.available >= self._need_pages(
            prompt_len, n_tokens, self._alloc.n_pages)

    def sched_release(self, b: int) -> None:
        """Return an evicted row's pages to the pool (host-side; the row's
        device-side table is cleared by the boundary's reset/insert before
        the next chunk runs)."""
        if self.paged and self._alloc is not None:
            self._alloc.free(self._row_pages.pop(b, ()))

    def sched_abort(self, b: int) -> None:
        """Release a LIVE, unfinished row mid-flight (client cancellation,
        expired deadline, injected fault).  Identical to the eviction-time
        release: the allocator is host state, so returning an unfinished
        row's pages never syncs the device — but the caller MUST reset the
        row (clearing its device-side block table) before the next chunk
        runs, or a same-boundary admission could write pages the aborted
        row still references.  The scheduler's dirty-reset ordering
        guarantees exactly that."""
        self.sched_release(b)

    @property
    def sched_pages_held(self) -> int:
        """Pages currently reserved by resident rows (0 when dense)."""
        if not self.paged:
            return 0
        return sum(len(p) for p in self._row_pages.values())

    def sched_pool_conserved(self) -> bool:
        """Page-leak audit: the allocator's free+held must equal the pool
        and agree with the engine's per-row bookkeeping.  True for dense
        engines and before the first sched admission."""
        if not self.paged or self._alloc is None:
            return True
        return (self._alloc.conserved
                and self._alloc.outstanding == self.sched_pages_held)

    def sched_drained(self) -> bool:
        """True when every page is back on the free list and no row holds
        a reservation — the zero-leak postcondition every drained stream
        (including aborted/faulted ones) must satisfy."""
        if not self.paged or self._alloc is None:
            return True
        return (not self._row_pages
                and self._alloc.available == self._alloc.n_pages)

    def _sched_pages(self, b: int, prompt_len: int, n_tokens: int):
        """Allocate row ``b``'s reservation (gated by ``sched_can_admit``),
        -1-padded to the static ``max_pages`` table width."""
        pages = self._alloc.alloc(self._need_pages(prompt_len, n_tokens,
                                                   self._alloc.n_pages))
        self._row_pages[b] = pages
        out = np.full((self.max_pages,), -1, np.int32)
        out[:len(pages)] = pages
        return jnp.asarray(out)

    # ---- chunked-prefill hook (runtime/scheduler.py prefill_chunk) -------
    def _extend_fn(self, C: int):
        """Per-piece-width jit of the prefill-extend row surgery."""
        if C not in self._extends:
            model = self.model
            tree = Tree.from_spec(chain_spec(C))

            # named (not a bare lambda) so compile-log audits (`python -m
            # repro.analysis.tracecount`) bucket it distinctly
            def prefill_extend(p, st, b, toks, nv):
                return _extend_row(model, p, st, b, toks, nv, tree)

            self._extends[C] = jax.jit(prefill_extend, donate_argnums=(1,))
        return self._extends[C]

    def sched_extend(self, state, b, tokens, n_valid):
        """One chunked-prefill piece: run ``tokens (1, C)`` (tail pieces
        right-padded; ``n_valid`` real entries) through the causal verify
        path against row ``b``'s existing cache and splice the piece's KVs
        in at the row's offset.  Returns (state, last-real-token device
        scalar — after the final piece that token is the request's first
        emission, and a drafted row additionally carries the
        ``cur_token``/``hidden`` of the last real position, so the finished
        slot is indistinguishable from a whole-prompt admission).  Compiled
        once per piece width C."""
        self._touch_bank()
        state, last, counts = self._extend_fn(int(tokens.shape[1]))(
            self.params, state, jnp.asarray(b, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(n_valid, jnp.int32))
        self._count("prefill", counts)
        return state, last

    def _count(self, phase, counts) -> None:
        """Hand a call's expert counts (still on the device) over to
        ``self.experts``."""
        if self.experts is not None:
            self.experts.add(phase, counts)


class DecodeEngine(_PagedPoolMixin):
    """ONE serving engine for every decode strategy.

    ``strategy`` picks what a step does (``DecodeStrategy.sequential()`` /
    ``DecodeStrategy.medusa(tree_spec)``); ``heads`` are required exactly
    when the strategy drafts.  ``chunk`` = K steps fused into one device
    call via ``lax.scan``; K=1 degenerates to the per-step host-synced
    loop.  ``paged=True`` swaps the bank's dense per-row KV for the shared
    page pool (``pool_pages`` total; default ``B * ceil(max_len /
    page_size)``, the dense-equivalent capacity — shrink it to serve a
    larger bank at fixed memory).

    Runtime strategy switching: ``set_strategy`` swaps the strategy between
    chunks (same draft kind only — the state carry differs); same-shape
    strategies reuse the compiled scans.  ``register_strategies`` arms a
    candidate set for the scheduler's adaptive mode and ratchets the paged
    reservation overshoot to the deepest candidate.  ``time_step`` measures
    one compiled step — ARCA's measured time source.

    ``backend`` picks the attention path: ``"pallas"`` (the kernels in
    ``kernels/``) or ``"ref"`` (the jnp reference); None serves Pallas on
    a TPU and the reference elsewhere.

    ``kv_dtype`` picks the paged pool's storage dtype — ``"int8"``
    quantizes pages with per-page dequant scales (runtime/cache.py),
    shrinking bytes/token ~3.5x so the same pool bytes reserve more
    tokens.  ``tree_kernel`` picks the paged verify kernel: ``"dense"``
    (fused page walk + tree block) or ``"sparse"`` (split quantized page
    walk + block-masked tree kernel, merged by the Eq.-1 rule);
    ``set_tree_kernel`` / ``time_step(tree_kernel=...)`` let ARCA
    measure both per shape."""

    def __init__(self, model, params, *, strategy: Optional[DecodeStrategy]
                 = None, heads=None, max_len=512, window=0, backend=None,
                 chunk=8, paged=False, page_size=16, pool_pages=None,
                 hcmp="inline", kv_dtype=None, tree_kernel="dense"):
        if strategy is None:
            if heads is not None:
                raise ValueError("an engine with draft heads needs an "
                                 "explicit DecodeStrategy.medusa(tree_spec)")
            strategy = DecodeStrategy.sequential()
        if (strategy.draft == "medusa") != (heads is not None):
            raise ValueError(f"strategy draft {strategy.draft!r} "
                             f"{'requires' if strategy.draft == 'medusa' else 'forbids'} "
                             "draft heads")
        if hcmp not in ("inline", "overlap"):
            raise ValueError(f"hcmp must be 'inline' or 'overlap', "
                             f"got {hcmp!r}")
        if hcmp == "overlap" and heads is None:
            raise ValueError("hcmp='overlap' needs a drafted strategy: the "
                             "sequential engine has no draft source to "
                             "disaggregate")
        kv_dtype = _kv_dtype(kv_dtype)
        if kv_dtype == jnp.int8 and not paged:
            raise ValueError("kv_dtype=int8 quantizes the PAGED pool "
                             "(per-page scales live on the page axis); "
                             "dense ring caches stay float — pass "
                             "paged=True")
        if tree_kernel not in ("dense", "sparse"):
            raise ValueError(f"tree_kernel must be 'dense' or 'sparse', "
                             f"got {tree_kernel!r}")
        if tree_kernel == "sparse" and not paged:
            raise ValueError("tree_kernel='sparse' splits the PAGED verify "
                             "path (quantized page walk + block-masked "
                             "tree kernel); dense caches use the fused "
                             "kernel — pass paged=True")
        self.kv_dtype = kv_dtype
        self.tree_kernel = tree_kernel
        self.model, self.params, self.heads = model, params, heads
        self.strategy = strategy
        # HCMP executor split (core/hcmp/executors.py): "overlap" routes
        # chunks through the disaggregated draft/verify runner, built
        # lazily.  The bank epoch versions the resident state: every
        # mutation (admission, reset, extend, strategy switch, a new
        # generate/time_step stream) bumps it, invalidating the runner's
        # cross-chunk pre-draft (mis-speculated overlaps are discarded
        # and redrafted -- outputs stay bit-identical either way).
        self.hcmp = hcmp
        self._hcmp_runner = None
        self._bank_epoch = 0
        self._registered: Dict[int, DecodeStrategy] = {}
        self._registered_depth = 0
        if window and getattr(model.cfg, "layer_types", None) is not None:
            raise ValueError("a stack with per-layer windows "
                             "(ModelConfig.layer_types) takes no "
                             "engine-wide window: pass window=0")
        self.max_len, self.window = max_len, window
        self.backend, self.chunk = _attn_backend(backend), chunk
        # routed-expert work of a sparse-expert model (stats["moe"])
        n_exp = getattr(model.cfg, "num_experts", 0)
        self.experts = ExpertCounters(model.cfg.num_layers) if n_exp \
            else None
        self._paged_init(paged=paged, page_size=page_size,
                         pool_pages=pool_pages)
        # every jit target below is a NAMED def (not a lambda): the
        # compile log (`jax_log_compiles`) reports the target's __name__,
        # and the tracecount audit diffs per-name compile counts against
        # the committed budget — `<lambda>` buckets would alias
        def prefill_full(p, h, b):
            return _prefill_state(model, p, h, b, max_len=max_len,
                                  window=window)

        self._prefill = jax.jit(prefill_full)
        self._chunks = {}           # K -> jitted K-step scan
        # state-threading jits donate their carried state: the cache (one
        # large pool when paged) is aliased in place, never copied
        self._insert = jax.jit(_insert_row, donate_argnums=(0,))
        self._reset = jax.jit(_reset_state_rows, donate_argnums=(0,))

        # fused admission: B=1 prefill + row splice in ONE device call (a
        # per-request dispatch on the scheduler's hot path)
        def admit_row(p, h, st, b, bt):
            return _admit_row(model, p, h, st, b, bt, max_len=max_len,
                              window=window)

        self._admit = jax.jit(admit_row, donate_argnums=(2,))
        if paged:
            # prompt-sized dense prefill: paginated right after (generate)
            # or spliced into the paged bank (admission) — never a full
            # (B, max_len) dense transient
            def prefill_prompt(p, h, b):
                return _prefill_state(model, p, h, b, max_len=1, window=0)

            def admit_paged(p, h, st, b, bt, pages):
                return _admit_row_paged(model, p, h, st, b, bt, pages)

            def insert_paged(st, b, row, pages):
                return _insert_row(st, b, row, pages=pages)

            self._prefill_prompt = jax.jit(prefill_prompt)
            self._prefills_paged = {}    # n_pages -> fused prefill+paginate
            self._admit_paged = jax.jit(admit_paged, donate_argnums=(2,))
            self._insert_paged = jax.jit(insert_paged, donate_argnums=(0,))

    # ---- strategy axis ---------------------------------------------------
    @property
    def tree(self) -> Tree:
        return self.strategy.tree

    @property
    def max_depth(self) -> int:
        return self.strategy.tree.max_depth

    @property
    def _overshoot(self) -> int:
        # worst case slots written past the budget: one full accepted chain
        # (1 for sequential); with runtime switching armed, the deepest
        # registered candidate (a switch must never outgrow a reservation)
        return max(self.strategy.tree.max_depth, self._registered_depth)

    def strategy_for(self, spec: TreeSpec) -> DecodeStrategy:
        """Build a DecodeStrategy of THIS engine's draft kind from a tree
        spec (the state carry differs across draft kinds, so an engine can
        only ever run strategies of its own kind)."""
        if self.heads is None:
            if spec.width != 1:
                raise ValueError("a draft-free engine can only run the "
                                 "degenerate width-1 strategy")
            return DecodeStrategy.sequential()
        return DecodeStrategy.medusa(spec)

    def set_strategy(self, strategy) -> None:
        """Swap the decode strategy WITHOUT dropping compiled steps (the
        strategy is a jit argument: same-shape strategies share one
        compiled scan).  Accepts a ``DecodeStrategy`` or a ``TreeSpec``;
        the draft kind must match the engine's.  Safe only at chunk
        boundaries — the scheduler's adaptive mode calls it there."""
        if isinstance(strategy, TreeSpec):
            strategy = self.strategy_for(strategy)
        if strategy.draft != self.strategy.draft:
            raise ValueError(f"cannot switch draft kind "
                             f"{self.strategy.draft!r} -> {strategy.draft!r}"
                             " (the state carry differs)")
        self.strategy = strategy
        self._touch_bank()

    # ---- HCMP executor split (core/hcmp/executors.py) --------------------
    @property
    def hcmp_capable(self) -> bool:
        """Whether this engine can run the disaggregated overlap schedule
        (it needs a draft source to put on the second executor)."""
        return self.heads is not None

    def set_hcmp(self, mode: str) -> None:
        """Switch the executor partition between chunks ("inline" |
        "overlap").  Safe only at chunk boundaries, like
        ``set_strategy``; bumps the bank epoch so a pre-draft computed
        under the other schedule is discarded."""
        if mode not in ("inline", "overlap"):
            raise ValueError(f"hcmp must be 'inline' or 'overlap', "
                             f"got {mode!r}")
        if mode == "overlap" and not self.hcmp_capable:
            raise ValueError("hcmp='overlap' needs a drafted strategy")
        self.hcmp = mode
        self._touch_bank()

    def set_tree_kernel(self, mode: str) -> None:
        """Switch the paged verify kernel between chunks ("dense" = fused
        page walk + tree block, "sparse" = split quantized page walk +
        block-masked tree kernel, merged by the Eq.-1 rule).  Safe only at
        chunk boundaries, like ``set_strategy``; the choice is a closure
        static of the compiled scans (``_chunk_fn`` keys on it) and of the
        overlap runner, which is rebuilt on change."""
        if mode not in ("dense", "sparse"):
            raise ValueError(f"tree_kernel must be 'dense' or 'sparse', "
                             f"got {mode!r}")
        if mode == "sparse" and not self.paged:
            raise ValueError("tree_kernel='sparse' needs a paged engine")
        if mode != self.tree_kernel:
            self.tree_kernel = mode
            self._hcmp_runner = None     # verify_front baked the old kernel
        self._touch_bank()

    def _touch_bank(self) -> None:
        """Version the resident bank: called by every mutation that makes
        a cross-chunk pre-draft stale (admission/insert/reset/extend, a
        strategy or partition switch, a new generate/time_step stream)."""
        self._bank_epoch += 1

    def _hcmp(self):
        if self._hcmp_runner is None:
            from repro.core.hcmp.executors import HcmpOverlapRunner
            self._hcmp_runner = HcmpOverlapRunner(
                self.model, self.heads, backend=self.backend,
                tree_kernel=self.tree_kernel)
        return self._hcmp_runner

    @property
    def hcmp_stats(self) -> Optional[dict]:
        """Overlap-runner counters (None until the runner exists)."""
        if self._hcmp_runner is None:
            return None
        return dict(self._hcmp_runner.stats, mode=self.hcmp)

    def _run_chunk(self, K, strategy, state, done, rem, eos_val):
        """Route one K-step chunk: the fused inline ``chunk_scan`` or the
        disaggregated overlap pipeline — same signature, bit-identical
        outputs (greedy verification commits the greedy chain whatever
        the draft's placement or timing)."""
        if self.hcmp == "overlap" and strategy.draft == "medusa":
            # the overlap pipeline keeps no expert counts
            return self._hcmp().run_chunk(self.params, strategy, state,
                                          done, rem, K, eos_val,
                                          self._bank_epoch) + (None,)
        return self._chunk_fn(K)(self.params, self.heads, strategy, state,
                                 done, rem, eos_val)

    def set_tree(self, tree_spec: TreeSpec) -> None:
        """Legacy alias of ``set_strategy`` (ARCA's ``measure_acceptance``
        swaps candidate trees through it)."""
        self.set_strategy(tree_spec)

    def register_strategies(self, specs) -> Dict[int, DecodeStrategy]:
        """Arm a candidate set for runtime switching: builds the
        DecodeStrategy per width ONCE (switches then reuse the same
        pytrees) and ratchets the paged reservation overshoot to the
        deepest candidate so a mid-request switch can never outgrow a
        row's page reservation.  ``specs``: {width: TreeSpec}."""
        self._registered = {int(w): self.strategy_for(sp)
                            for w, sp in specs.items()}
        self._registered_depth = max(
            [s.tree.max_depth for s in self._registered.values()],
            default=0)
        return self._registered

    # ---- the ONE chunk driver --------------------------------------------
    def _chunk_fn(self, K: int):
        # keyed by (K, tree_kernel): the verify kernel choice is baked into
        # the compiled scan (a closure static, like ``backend``), so a
        # runtime switch lands in a different compile-cache entry instead
        # of silently reusing the other kernel's scan
        key = (K, self.tree_kernel)
        if key not in self._chunks:
            model, backend = self.model, self.backend
            tree_kernel = self.tree_kernel

            def chunk_scan(p, h, strat, state, done, rem, eos):
                def body(carry, _):
                    state, done, rem = carry
                    # capacity guard BEFORE the step: a commit may write up
                    # to max_depth slots (1 for sequential), so freeze once
                    # the ring cannot take a worst case without wrapping
                    done = done | (rem <= 0) | \
                        (capacity_left(state.cache) < strat.tree.max_depth)
                    active = ~done
                    if strat.draft == "none":       # static: strategy meta
                        state, emitted, n = _seq_step(model, p, state,
                                                      backend=backend,
                                                      active=active)
                        counts = None
                    else:
                        state, emitted, n, counts = spec_step(
                            model, p, h, strat.tree, state, backend=backend,
                            tree_kernel=tree_kernel, active=active,
                            return_counts=True)
                    idx = jnp.arange(emitted.shape[1])[None, :]
                    valid = idx < n[:, None]
                    is_eos = valid & (emitted == eos)
                    has_eos = jnp.any(is_eos, axis=1)
                    # truncate each sequence's emission at its first EOS
                    n_cut = jnp.where(has_eos,
                                      jnp.argmax(is_eos, axis=1) + 1, n)
                    n_eff = jnp.where(active, n_cut, 0)
                    emitted = jnp.where(idx < n_eff[:, None], emitted, eos)
                    done = done | has_eos
                    rem = rem - n_eff
                    return (state, done, rem), (emitted, n_eff, counts)

                (state, done, rem), (toks, ns, counts) = jax.lax.scan(
                    body, (state, done, rem), None, length=K)
                # toks: (K, B, Dmax) eos-padded; ns: (K, B) accepted counts;
                # counts: (K, 3) expert counts per step (None: no experts)
                return state, done, rem, toks, ns, counts

            # donate the scan carry (state incl. the KV pool, done, rem):
            # in-place chunk updates, no per-chunk cache copy
            self._chunks[key] = jax.jit(chunk_scan, donate_argnums=(3, 4, 5))
        return self._chunks[key]

    def _prefill_paged_fn(self, n_total: int):
        if n_total not in self._prefills_paged:
            model, ps = self.model, self.page_size
            kvdt = self.kv_dtype

            def prefill_paged(p, h, b, tables):
                st, _ = _prefill_state(model, p, h, b, max_len=1, window=0)
                return type(st)(
                    cache=paginate_cache(st.cache, tables, page_size=ps,
                                         n_pages=n_total, kv_dtype=kvdt),
                    cur_token=st.cur_token, hidden=st.hidden)

            self._prefills_paged[n_total] = jax.jit(prefill_paged)
        return self._prefills_paged[n_total]

    # ---- batch generation ------------------------------------------------
    def generate(self, batch, n_tokens, *, eos: Optional[int] = None,
                 chunk: Optional[int] = None):
        """``n_tokens``: int or (B,) per-sequence budgets.  Returns
        ``(out, stats)``; rows past their budget / EOS / capacity freeze
        pad with ``eos`` (-1 if None) and ``stats["n_emitted"]`` has the
        real per-sequence counts.  Drafted engines return a 1-D token
        array at B=1 (legacy ``SpeculativeEngine`` shape); the sequential
        strategy always returns ``(B, max_budget)``."""
        K = chunk or self.chunk
        eos_val = _eos_scalar(eos)
        B = int(batch["tokens"].shape[0])
        budget = _budget(n_tokens, B)
        self._touch_bank()            # new stream: stale pre-drafts die
        if self.paged:
            tables, n_total = self._reserve_tables(batch, budget)
            state = self._prefill_paged_fn(n_total)(
                self.params, self.heads, batch, tables)
        else:
            state, _ = self._prefill(self.params, self.heads, batch)
        n_max = int(budget.max())
        # prologue sync: materialize the prefill's first token + done mask
        # reprolint: disable=R3 (intended post-prefill sync)
        first = np.asarray(state.cur_token)
        outs = [[int(first[b])] for b in range(B)]
        done = state.cur_token == eos_val
        rem = jnp.asarray(budget - 1)
        # reprolint: disable=R3 (intended post-prefill sync)
        done_np, rem_np = np.asarray(done), budget - 1
        accepts, times = [], []

        while np.any(~done_np & (rem_np > 0)):
            # every live step emits >= 1 token, so the largest remaining
            # budget bounds the steps still needed — no full-K tail chunks
            need = int(rem_np[~done_np & (rem_np > 0)].max())
            t0 = time.perf_counter()
            state, done, rem, toks, ns, _ = self._run_chunk(
                _pow2_chunk(K, need), self.strategy, state, done, rem,
                eos_val)
            # ONE host sync per chunk: this block is the whole budget
            toks_np = np.asarray(toks)    # reprolint: disable=R3 (chunk sync)
            ns_np = np.asarray(ns)        # reprolint: disable=R3 (chunk sync)
            # reprolint: disable=R3 (chunk sync)
            done_np, rem_np = np.asarray(done), np.asarray(rem)
            times.append(time.perf_counter() - t0)
            for k in range(ns_np.shape[0]):
                for b in range(B):
                    m = int(ns_np[k, b])
                    if m and len(outs[b]) < budget[b]:
                        # count only steps whose tokens are (at least partly)
                        # kept: overshoot steps past n_tokens would bias the
                        # acceptance stats ARCA's evaluator consumes
                        accepts.append(m)
                        outs[b].extend(int(x) for x in toks_np[k, b, :m])

        n_emitted = np.asarray(
            [min(len(outs[b]), int(budget[b])) for b in range(B)], np.int32)
        stats = _stats(accepts, times)
        stats["chunk"] = K
        stats["n_emitted"] = n_emitted
        stats["emitted_total"] = int(n_emitted.sum())
        out = np.full((B, n_max), int(eos_val), np.int32)
        for b in range(B):
            # reprolint: disable=R3 (outs is a host list, not a device array)
            seq = np.asarray(outs[b][:budget[b]], np.int32)
            out[b, :len(seq)] = seq
        if B == 1 and self.strategy.draft == "medusa":
            return out[0], stats
        return out, stats

    # ---- measured step time (ARCA's time source) -------------------------
    def time_step(self, strategy: Optional[DecodeStrategy] = None, *,
                  batch: int = 1, prompt_len: int = 16, reps: int = 3,
                  chunk: Optional[int] = None,
                  hcmp: Optional[str] = None,
                  tree_kernel: Optional[str] = None) -> float:
        """Best-of-``reps`` wall time of ONE decode step under ``strategy``
        (default: the current one), measured through the engine's COMPILED
        chunk scan on a dummy prompt — the strategy is a jit argument, so
        the timed function is exactly the deployed one.  Timed at the
        serving chunk cadence (``chunk`` steps per dispatch, divided out);
        feeds ``core/arca.py profile_engine`` -> ``choose_strategy``.

        ``hcmp`` overrides the executor partition for this measurement
        ("inline" | "overlap") — ARCA times both and picks the partition
        the same way it picks the speculative strategy.  ``tree_kernel``
        ("dense" | "sparse") likewise overrides the paged verify kernel,
        so ARCA measures the fused vs split page walk per shape instead
        of trusting an analytic crossover."""
        strategy = strategy or self.strategy
        K = chunk or self.chunk
        prev_hcmp = self.hcmp
        prev_tk = self.tree_kernel
        if hcmp is not None:
            self.set_hcmp(hcmp)
        if tree_kernel is not None:
            self.set_tree_kernel(tree_kernel)
        try:
            self._touch_bank()        # measurement stream, not the bank
            bd = {"tokens": jnp.zeros((batch, prompt_len), jnp.int32)}
            if self.paged:
                budget = np.full((batch,), self.max_len, np.int64)
                tables, n_total = self._reserve_tables(bd, budget)
                state = self._prefill_paged_fn(n_total)(
                    self.params, self.heads, bd, tables)
            else:
                state, _ = self._prefill(self.params, self.heads, bd)
            done = jnp.zeros((batch,), bool)
            rem = jnp.full((batch,), 1 << 30, jnp.int32)
            eos = _eos_scalar(None)

            def step(st, dn, rm):
                return self._run_chunk(K, strategy, st, dn, rm, eos)

            # warm-up compiles; the donated carry is rebound from the
            # outputs
            state, done, rem, toks, *_ = step(state, done, rem)
            # reprolint: disable=R3 (timing harness)
            jax.block_until_ready(toks)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                state, done, rem, toks, *_ = step(state, done, rem)
                # this IS the measurement: ARCA times the compiled step
                # reprolint: disable=R3 (timing harness)
                jax.block_until_ready(toks)
                best = min(best, time.perf_counter() - t0)
            return best / K
        finally:
            if hcmp is not None:
                self.set_hcmp(prev_hcmp)
            if tree_kernel is not None:
                self.set_tree_kernel(prev_tk)

    # ---- continuous-batching slot protocol (runtime/scheduler.py) --------
    def sched_prefill(self, batch):
        """B=1 prefill -> opaque row state.  Paged engines prefill at
        prompt size (the dense row is a splice source, not a resident)."""
        fn = self._prefill_prompt if self.paged else self._prefill
        row, counts = fn(self.params, self.heads, batch)
        self._count("prefill", counts)
        return row

    @staticmethod
    def sched_first(row):
        return int(np.asarray(row.cur_token)[0])

    def sched_blank(self, row, batch):
        self._touch_bank()
        if self.paged:
            n_total = self.pool_pages or batch * self.max_pages
            self._alloc = PageAllocator(n_total)
            self._row_pages = {}
            bank = blank_paged_rows(row.cache, batch,
                                    page_size=self.page_size,
                                    n_pages=n_total, max_len=self.max_len,
                                    kv_dtype=self.kv_dtype)
        else:
            bank = tile_rows(row.cache, batch)
        hid = None if row.hidden is None else \
            jnp.repeat(row.hidden, batch, axis=0)
        return type(row)(cache=bank,
                         cur_token=jnp.repeat(row.cur_token, batch, axis=0),
                         hidden=hid)

    def sched_insert(self, state, b, row, *, prompt_len=None, n_tokens=None):
        self._touch_bank()
        if self.paged:
            pages = self._sched_pages(b, prompt_len, n_tokens)
            return self._insert_paged(state, jnp.asarray(b, jnp.int32), row,
                                      pages)
        return self._insert(state, jnp.asarray(b, jnp.int32), row)

    def sched_admit(self, state, b, batch, *, n_tokens=None,
                    reserve_len=None):
        """Fused prefill+insert; returns (state, first-token device scalar —
        unsynced, the caller materializes it lazily).  ``reserve_len``
        overrides the page reservation's prompt length — chunked prefill
        admits only the FIRST piece here but must reserve for the whole
        prompt."""
        self._touch_bank()
        if self.paged:
            plen = reserve_len if reserve_len is not None \
                else _prompt_len(batch)
            pages = self._sched_pages(b, plen, n_tokens)
            state, first, counts = self._admit_paged(
                self.params, self.heads, state, jnp.asarray(b, jnp.int32),
                batch, pages)
        else:
            state, first, counts = self._admit(
                self.params, self.heads, state, jnp.asarray(b, jnp.int32),
                batch)
        self._count("prefill", counts)
        return state, first

    def sched_reset(self, state, b):
        self._touch_bank()
        mask = np.zeros((int(state.cur_token.shape[0]),), bool)
        mask[b] = True
        return self._reset(state, mask)

    def sched_step(self, state, done, rem, K, eos_val):
        # eos arrives as a Python int from the scheduler but as an int32
        # array from generate(); coerce so both paths key the SAME
        # compile-cache entry of the chunk fn (R7 retrace audit)
        state, done, rem, toks, ns, counts = self._run_chunk(
            K, self.strategy, state, done, rem,
            jnp.asarray(eos_val, jnp.int32))
        self._count("decode", counts)
        return state, done, rem, (toks, ns)

    def sched_fetch(self, raw):
        """The chunk's ``(toks, ns)`` copied to the host, after the
        boundary's ``done``/``rem`` sync has waited out the chunk; the
        expert counts handed over since the last fetch (the chunk's and
        the admissions' before it, all finished) are read with it."""
        if self.experts is not None:
            self.experts.fold()
        return jax.device_get(raw)

    @staticmethod
    def sched_emitted(raw):
        # per row, the tokens of the chunk's token block; a block still on
        # the device is materialized here exactly once
        # reprolint: disable=R3 (intended boundary sync)
        toks, ns = (np.asarray(x) for x in raw)
        K, B = ns.shape
        out = [[] for _ in range(B)]
        for k in range(K):
            for b in range(B):
                m = int(ns[k, b])
                if m:
                    out[b].extend(int(x) for x in toks[k, b, :m])
        return out


# ===========================================================================
# legacy entry points: thin constructor aliases over DecodeEngine
# ===========================================================================
class BatchEngine(DecodeEngine):
    """Sequential baseline = ``DecodeEngine`` pinned to the degenerate
    ``DecodeStrategy.sequential()`` (chain_spec(width=1), no draft).
    Output- and protocol-identical to the pre-unification BatchEngine."""

    def __init__(self, model, params, *, max_len=512, window=0,
                 backend=None, chunk=8, paged=False, page_size=16,
                 pool_pages=None, kv_dtype=None):
        super().__init__(model, params,
                         strategy=DecodeStrategy.sequential(),
                         max_len=max_len, window=window, backend=backend,
                         chunk=chunk, paged=paged, page_size=page_size,
                         pool_pages=pool_pages, kv_dtype=kv_dtype)


class SpeculativeEngine(DecodeEngine):
    """Ghidorah speculative serving = ``DecodeEngine`` with a Medusa-draft
    strategy built from ``tree_spec``.  Output- and protocol-identical to
    the pre-unification SpeculativeEngine."""

    def __init__(self, model, heads, params, tree_spec: TreeSpec, *,
                 max_len=512, window=0, backend=None, chunk=8, paged=False,
                 page_size=16, pool_pages=None, hcmp="inline",
                 kv_dtype=None, tree_kernel="dense"):
        super().__init__(model, params, heads=heads,
                         strategy=DecodeStrategy.medusa(tree_spec),
                         max_len=max_len, window=window, backend=backend,
                         chunk=chunk, paged=paged, page_size=page_size,
                         pool_pages=pool_pages, hcmp=hcmp,
                         kv_dtype=kv_dtype, tree_kernel=tree_kernel)


def _stats(accepts, times):
    accepts = np.asarray(accepts)
    return {
        "acceptance_length": float(np.mean(accepts)) if accepts.size else 0.0,
        "steps": int(accepts.size),
        "step_times": times,
    }


def measure_acceptance(model, heads, params, tree_spec: TreeSpec, prompts,
                       n_tokens=64, *, max_len=512,
                       engine: Optional[DecodeEngine] = None) -> float:
    """Empirical acceptance length over a prompt set (ARCA's brute-force
    refinement evaluator + Table-I measurement).

    Pass ``engine`` to reuse a constructed engine across candidate trees:
    the strategy is swapped via ``set_tree`` and the jitted step is shared
    for same-shape trees, so ARCA's evaluator does not pay compile time
    per candidate.
    """
    if engine is None:
        engine = SpeculativeEngine(model, heads, params, tree_spec,
                                   max_len=max_len)
    else:
        engine.set_tree(tree_spec)
    als = []
    for batch in prompts:
        _, stats = engine.generate(batch, n_tokens)
        als.append(stats["acceptance_length"])
    return float(np.mean(als))
