"""Randomized scheduler fuzz: random traces through the continuous
scheduler must be indistinguishable, per request, from solo B=1 runs.

Each drawn example is a full serve(): random arrivals, prompt lengths,
budgets, admission policy (fifo/sjf/lpt), layout (dense / paged fp32 /
paged int8 — the quantized arm obeys the SAME solo oracle, since
quantize-on-write is deterministic per resident), engine
(sequential/speculative), bank width and chunked-prefill setting.  The
oracle is ``engine.generate`` on each request alone — the scheduler may
only change WHEN a request runs, never WHAT it emits:

  * results are returned for every request exactly once, in request order;
  * per-request tokens are bit-identical to the solo run (admission
    order, slot reuse, chunked prefill and neighbors never perturb a
    sequence) and ``n_emitted`` matches the solo count (a pool-capped
    reservation freezes at the same shortfall solo does);
  * ``n_emitted <= budget`` and the token array carries exactly
    ``n_emitted`` entries — no emission after done;
  * a drained paged serve returns every page (free == pool).

A second fuzz stresses ``PageAllocator`` itself with interleaved
reserve/release orderings (fragmentation, aborted runs): free + reserved
must equal the pool at every step and a full drain must restore the
initial free list.

A third fuzz drives the request LIFECYCLE through the stepping API:
random cancellations (``abort()`` at random boundaries), random
deadlines and injected admission-exhaustion/stall faults mid-trace.
Every request must land in exactly one typed terminal state, every
emitted token array must be a bit-identical PREFIX of the solo run
(DONE requests the full solo output), and a drained paged pool must
conserve every page through mid-flight abort/timeout cleanup.

Seeds are fixed (``tests/_mini_hypothesis.py`` derives them from the test
name), so tier-1/CI replays the exact same traces every run.
"""
import jax
import numpy as np
import pytest

from _mini_hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.speculative import tree as T
from repro.core.speculative.medusa import init_medusa
from repro.models.api import get_model
from repro.runtime.cache import PageAllocator
from repro.runtime.engine import BatchEngine, SpeculativeEngine
from repro.runtime.faults import FaultPlan
from repro.runtime.scheduler import (CANCELLED, DONE, TERMINAL_STATES,
                                     AdmissionPolicy, ContinuousScheduler,
                                     Request, get_policy)

MAX_LEN = 64
PAGE_SIZE = 8
POOL_PAGES = {False: None, True: 8}    # two 4-page reservations: a third
                                       # concurrent request gets DEFERRED
PROMPT_LENS = (3, 6, 14)               # small set: bounds prefill compiles
BUDGETS = (1, 2, 5, 9)
PREFILL_CHUNK = 4

_ENGINES = {}
_SOLO = {}                             # (engine key, prompt, budget) -> out


def _engine(kind, paged, kv_dtype=None):
    key = (kind, paged, kv_dtype)
    if key not in _ENGINES:
        cfg = get_config("qwen2-0.5b").reduced()
        model = get_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        kw = dict(max_len=MAX_LEN, chunk=4, paged=paged,
                  page_size=PAGE_SIZE, pool_pages=POOL_PAGES[paged],
                  kv_dtype=kv_dtype)
        if kind == "spec":
            heads = init_medusa(cfg, jax.random.PRNGKey(7))
            spec = T.build_tree(
                T.default_accs(cfg.medusa_heads, cfg.medusa_top_k), 8)
            eng = SpeculativeEngine(model, heads, params, spec, **kw)
        else:
            eng = BatchEngine(model, params, **kw)
        _ENGINES[key] = (cfg, eng)
    return _ENGINES[key]


def _solo(key, eng, req):
    skey = (key, req.tokens.tobytes(), req.n_tokens)
    if skey not in _SOLO:
        out, stats = eng.generate({"tokens": req.tokens[None]}, req.n_tokens)
        _SOLO[skey] = (np.atleast_2d(out)[0], int(stats["n_emitted"][0]))
    return _SOLO[skey]


LAYOUTS = [(False, None), (True, None), (True, "int8")]
# (paged, kv_dtype): the int8 arm serves through the SAME solo-oracle
# contract — quantize-on-write is deterministic per resident, so the
# scheduler still may not change WHAT a request emits, only when.


@settings(max_examples=8, deadline=None)
@given(ex=st.tuples(
    st.integers(1, 6),                         # number of requests
    st.integers(0, 2 ** 31 - 1),               # trace seed
    st.sampled_from(["seq", "spec"]),
    st.sampled_from(LAYOUTS),                  # (paged, kv_dtype)
    st.sampled_from(["fifo", "sjf", "lpt"]),
    st.sampled_from([0, PREFILL_CHUNK]),
    st.sampled_from([2, 3]),                   # bank width B
))
def test_fuzz_continuous_matches_solo(ex):
    n, seed, kind, (paged, kv_dtype), policy, prefill_chunk, B = ex
    if kv_dtype == "int8":
        # frozen-first-write page scales make the quantized values depend
        # on prefill chunk boundaries (a partial first chunk arms the
        # scale, later chunks clip under it), so bit-parity with the
        # whole-prompt solo oracle is only guaranteed unchunked
        prefill_chunk = 0
    cfg, eng = _engine(kind, paged, kv_dtype)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        reqs.append(Request(
            req_id=i,
            tokens=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            n_tokens=int(rng.choice(BUDGETS)),
            arrival=float(rng.choice([0.0, 0.02, 0.05]))))
    sched = ContinuousScheduler(eng, batch=B, policy=policy,
                                prefill_chunk=prefill_chunk)
    results, stats = sched.serve(reqs)

    # every request exactly once, in request order
    assert [r.req_id for r in results] == [r.req_id for r in reqs]
    assert stats["admitted"] == n
    for r, req in zip(results, reqs):
        solo_toks, solo_n = _solo((kind, paged, kv_dtype), eng, req)
        assert r.n_emitted <= req.n_tokens
        assert len(r.tokens) == r.n_emitted       # no emission after done
        assert r.n_emitted == solo_n, (r.req_id, r.n_emitted, solo_n)
        np.testing.assert_array_equal(
            r.tokens, solo_toks[:solo_n],
            err_msg=f"req {r.req_id} (policy={policy}, paged={paged}, "
                    f"kv_dtype={kv_dtype}, chunked={prefill_chunk}, B={B})")
    if paged:                                     # full drain returns pages
        assert eng._alloc.available == eng._alloc.n_pages
    if kv_dtype == "int8":
        # freed pages may keep stale ARMED scales (reset_rows must not
        # touch pool scales — see runtime/cache.py), but every row still
        # holding a reservation after drain would be a leak
        kv = sched.last_state.cache.kv
        assert np.all(np.asarray(kv.block_table) == -1)


@settings(max_examples=8, deadline=None)
@given(ex=st.tuples(
    st.integers(2, 6),                         # number of requests
    st.integers(0, 2 ** 31 - 1),               # lifecycle seed
    st.sampled_from(["seq", "spec"]),
    st.sampled_from(LAYOUTS),                  # (paged, kv_dtype)
    st.sampled_from([2, 3]),                   # bank width B
))
def test_fuzz_lifecycle_terminal_and_conserved(ex):
    """Random cancels/deadlines/faults mid-trace: every request ends in
    exactly one typed terminal state, emitted tokens are always a
    bit-identical prefix of the solo run, and the paged pool conserves
    every page through mid-flight abort and timeout cleanup."""
    n, seed, kind, (paged, kv_dtype), B = ex
    cfg, eng = _engine(kind, paged, kv_dtype)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        reqs.append(Request(
            req_id=i,
            tokens=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            n_tokens=int(rng.choice(BUDGETS))))
    abort_at = {}                          # req_id -> boundary to cancel at
    for r in reqs:
        u = rng.random()
        if u < 0.35:
            abort_at[r.req_id] = int(rng.integers(1, 7))
        elif u < 0.5:
            r.deadline = float(rng.random() * 0.003)   # expires early
    plan = FaultPlan(seed=seed,
                     stall_rate=float(rng.choice([0.0, 0.2])),
                     stall_s=0.001,
                     exhaust_rate=float(rng.choice([0.0, 0.3])))
    sched = ContinuousScheduler(eng, batch=B, faults=plan.injector("fz"))
    sched.start(reqs)
    i = 0
    while sched.has_work:
        i += 1
        assert i < 500, "lifecycle trace did not converge"
        for rid, bnd in abort_at.items():
            if bnd == i:
                sched.abort(rid)
        sched.boundary()
    results, stats = sched.finish(reqs)

    assert [r.req_id for r in results] == [r.req_id for r in reqs]
    for r, req in zip(results, reqs):
        assert r.state in TERMINAL_STATES
        solo_toks, solo_n = _solo((kind, paged, kv_dtype), eng, req)
        assert len(r.tokens) == r.n_emitted <= solo_n
        np.testing.assert_array_equal(
            r.tokens, solo_toks[:r.n_emitted],
            err_msg=f"req {r.req_id} state={r.state} (kind={kind}, "
                    f"paged={paged}, kv_dtype={kv_dtype}, B={B})")
        if r.state == DONE:                # full solo output, nothing less
            assert r.n_emitted == solo_n
        if r.state == CANCELLED:
            assert req.req_id in abort_at  # only injected cancels
    assert sum(stats["states"].values()) == n
    if paged:                              # drained pool conserves pages
        assert eng._alloc.available == eng._alloc.n_pages
        assert eng.sched_pool_conserved() and eng.sched_drained()


@settings(max_examples=30, deadline=None)
@given(ex=st.tuples(st.integers(0, 2 ** 31 - 1),   # op-sequence seed
                    st.integers(4, 24),            # pool size
                    st.integers(5, 40)))           # number of ops
def test_fuzz_page_allocator_conservation(ex):
    """Interleaved reserve/release stress: free + reserved == pool at every
    step, fragmented release orderings reuse pages, and a full drain (an
    aborted run's cleanup) restores the initial free list."""
    seed, n_pages, n_ops = ex
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(n_pages)
    initial = list(alloc._free)
    held = []                                      # outstanding reservations
    for _ in range(n_ops):
        n_held = sum(len(h) for h in held)
        assert alloc.available + n_held == n_pages   # conservation
        if held and rng.random() < 0.4:
            # release a random (not necessarily oldest) reservation:
            # fragments the free list
            alloc.free(held.pop(int(rng.integers(len(held)))))
            continue
        want = int(rng.integers(1, max(n_pages // 2, 2)))
        if want > alloc.available:
            with pytest.raises(RuntimeError):
                alloc.alloc(want)
            pages = alloc.alloc_upto(want)         # partial reservation
        else:
            pages = alloc.alloc(want)
        assert len(set(pages)) == len(pages)       # no page handed out twice
        for other in held:
            assert not set(pages) & set(other)
        if pages:
            held.append(pages)
    for h in held:                                 # drain
        alloc.free(h)
    assert alloc._free == initial
    # double free is rejected
    if n_pages:
        got = alloc.alloc(1)
        alloc.free(got)
        with pytest.raises(RuntimeError):
            alloc.free(got)


class _Probe:
    """Engine stand-in for pure-policy fuzz: everything arrived is fundable
    unless its footprint exceeds ``limit``."""

    def __init__(self, limit):
        self.limit = limit

    def can_admit(self, r):
        return len(r.tokens) + r.n_tokens <= self.limit

    @staticmethod
    def footprint(r):
        return len(r.tokens) + r.n_tokens


@settings(max_examples=40, deadline=None)
@given(ex=st.tuples(st.integers(0, 2 ** 31 - 1),   # trace seed
                    st.integers(1, 10),            # pending length
                    st.sampled_from(["fifo", "sjf", "lpt"]),
                    st.integers(4, 30)))           # fundability limit
def test_fuzz_policy_pick_contract(ex):
    """Host-side policy contract, no model: a pick is always an ARRIVED,
    fundable request; FIFO never skips its head; SJF/LPT pick the
    smallest/largest fundable footprint with FIFO tie-breaks; bootstrap
    ignores fundability."""
    seed, n, name, limit = ex
    rng = np.random.default_rng(seed)
    now = 1.0
    pending = sorted(
        (Request(req_id=i, tokens=np.zeros(int(rng.integers(1, 16)),
                                           np.int32),
                 n_tokens=int(rng.integers(1, 16)),
                 arrival=float(rng.choice([0.0, 0.5, 2.0])))
         for i in range(n)), key=lambda r: (r.arrival, r.req_id))
    probe = _Probe(limit)
    policy = get_policy(name)
    idx = policy.pick(pending, now, probe.can_admit, probe.footprint,
                      bootstrap=False)
    arrived = [r for r in pending if r.arrival <= now]
    fundable = [r for r in arrived if probe.can_admit(r)]
    if name == "fifo":
        head_ok = (pending[0].arrival <= now
                   and probe.can_admit(pending[0]))
        assert (idx == 0) if head_ok else (idx is None)
    elif not fundable:
        assert idx is None
    else:
        picked = pending[idx]
        assert picked.arrival <= now and probe.can_admit(picked)
        best = (min if name == "sjf" else max)(
            probe.footprint(r) for r in fundable)
        assert probe.footprint(picked) == best
        ties = [r for r in fundable if probe.footprint(r) == best]
        assert picked.req_id == min(
            ties, key=lambda r: (r.arrival, r.req_id)).req_id
    # bootstrap: fundability is ignored, arrival is not
    bidx = policy.pick(pending, now, probe.can_admit, probe.footprint,
                       bootstrap=True)
    if arrived:
        assert bidx is not None and pending[bidx].arrival <= now
    else:
        assert bidx is None


def test_policy_registry():
    assert get_policy("sjf").name == "sjf"
    assert isinstance(get_policy(AdmissionPolicy()), AdmissionPolicy)
    with pytest.raises(ValueError):
        get_policy("srpt")
