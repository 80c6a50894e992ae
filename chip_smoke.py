#!/usr/bin/env python3
"""Bring-up smoke of the Ghidorah serving path on one TPU chip.

    python3 chip_smoke.py

Runs every phase in this one process (a chip belongs to one process at a
time) and fails on the first phase that fails:

  (a) device report — platform, device kind, device count, JAX version;
      anything but a TPU is a failure, never a fallback;
  (b) kernel parity — each Pallas kernel compiled for the chip (never
      interpreted) at Qwen2-0.5B head shapes against its ``kernels/ref.py``
      oracle;
  (c) lossless check — Qwen2-0.5B in float32 at the highest matmul
      precision: greedy Ghidorah tokens (width 8, paged, Pallas) equal
      greedy sequential tokens, and the Pallas verify logits match the
      reference backend's;
  (d) served path — Qwen2-0.5B in bfloat16 at full width, random weights
      from a seed, Poisson requests through ``repro.launch.serve.main``:
      sequential, Ghidorah over a float and an int8 page pool, the split
      sparse-tree verify kernel, the HCMP overlap schedule with its
      inline-parity gate, and ``--width 0`` (the width ARCA picks by
      timing each candidate's compiled step on the chip).

Each phase prints one line with its wall seconds and, apart, the seconds
JAX spent tracing, lowering and compiling.  Token counts, acceptance
lengths and times printed here are smoke numbers, not benchmark results.
The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
# the lossless check's tolerance on Pallas-vs-reference verify logits
# (float32, highest precision), and the largest top-2 logit gap at which a
# greedy divergence is read as a numerical tie rather than a bug
LOGIT_TOL = 1e-3
TIE_GAP = 1e-3


class PhaseFailed(Exception):
    pass


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while it is
    installed: the union of its ``/jax/core/compile/*`` duration events.
    A jit traced inside another's trace reports a span nested in the
    outer one, so the spans are merged, not summed."""

    def __init__(self):
        self._spans = []

    def __call__(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            self._spans.append((end - duration, end))

    @property
    def seconds(self):
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


def run_phase(name, fn, clock):
    """Run one phase; print its line; raise PhaseFailed on any failure,
    ``SystemExit`` from a gate of ``serve.main`` included."""
    t0, c0 = time.perf_counter(), clock.seconds
    try:
        detail = fn()
    except (Exception, SystemExit) as e:           # noqa: BLE001
        traceback.print_exc()
        print(f"[chip_smoke] {name}: FAIL after "
              f"{time.perf_counter() - t0:.1f}s: {e!r}", flush=True)
        raise PhaseFailed(name) from e
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    print(f"[chip_smoke] {name}: pass in {wall:.1f}s (compile {comp:.1f}s, "
          f"run {wall - comp:.1f}s): {detail}", flush=True)
    return detail


# --------------------------------------------------------------------------
# (a) device report
# --------------------------------------------------------------------------
def device_report():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise PhaseFailed(f"JAX found no TPU (device 0 is {dev.platform!r}, "
                          f"{dev.device_kind!r}); this smoke runs only on "
                          f"the chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__}


# --------------------------------------------------------------------------
# (b) kernel parity
# --------------------------------------------------------------------------
def _tree_mask(W, rng):
    import numpy as np
    parent = [-1] + [int(rng.integers(0, i)) for i in range(1, W)]
    mask = np.zeros((W, W), bool)
    depth = np.zeros(W, np.int32)
    for i in range(W):
        j = i
        while j >= 0:
            mask[i, j] = True
            depth[i] += j != i
            j = parent[j]
    return mask, depth


def kernel_parity(*, B=4, W=8, Hq=14, Hkv=2, hd=64, S=1024, ps=16,
                  n_pages=48, interpret=False):
    """Each kernel entry point, compiled (``interpret=False``) and checked
    for a Mosaic custom call, against its oracle on one seeded input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref as KR
    from repro.kernels import sparse_tree as KS
    from repro.kernels import tree_attention as KT
    from repro.models import common as cm

    rng = np.random.default_rng(0)
    bf = jnp.bfloat16

    def normal(*shape, dtype=bf):
        return jnp.asarray(rng.normal(size=shape), dtype)

    q, kn, vn = normal(B, W, Hq, hd), normal(B, W, Hkv, hd), \
        normal(B, W, Hkv, hd)
    mask_np, depth = _tree_mask(W, rng)
    mask = jnp.asarray(mask_np)
    # dense ring rows at diverged positions
    fills = np.asarray([S - 1 - 37 * b for b in range(B)], np.int32)
    key_pos = np.where(np.arange(S)[None] < fills[:, None],
                       np.arange(S)[None], -1).astype(np.int32)
    q_pos = jnp.asarray(fills[:, None] + depth[None], jnp.int32)
    lo = jnp.full_like(q_pos, -1)
    ck, cv = normal(B, S, Hkv, hd), normal(B, S, Hkv, hd)
    # fragmented paged rows, one partial page each
    maxp = 16
    P = n_pages + 1
    table = np.full((B, maxp), -1, np.int32)
    pfill = np.zeros(B, np.int32)
    perm = rng.permutation(n_pages)
    for b in range(B):
        n_res = 4 + 2 * b
        table[b, :n_res] = perm[b * 10:b * 10 + n_res]
        pfill[b] = n_res * ps - 5
    pkey = np.where(np.arange(maxp * ps)[None] < pfill[:, None],
                    np.arange(maxp * ps)[None], -1).astype(np.int32)
    pq_pos = jnp.asarray(pfill[:, None] + depth[None], jnp.int32)
    pool_k, pool_v = normal(P, ps, Hkv, hd), normal(P, ps, Hkv, hd)
    ones = jnp.ones((P, Hkv), jnp.float32)

    def quantize(pool):
        amax = jnp.max(jnp.abs(pool.astype(jnp.float32)), axis=(1, 3))
        scale = amax / 127.0
        qp = jnp.round(pool.astype(jnp.float32)
                       / jnp.maximum(scale, 1e-30)[:, None, :, None])
        return jnp.clip(qp, -127, 127).astype(jnp.int8), scale

    qk, sk = quantize(pool_k)
    qv, sv = quantize(pool_v)
    table, pkey, key_pos = map(jnp.asarray, (table, pkey, key_pos))

    def compiled(fn, *args):
        exe = jax.jit(fn).lower(*args).compile()
        if not interpret and "tpu_custom_call" not in exe.as_text():
            raise AssertionError(f"{fn.__name__}: no Mosaic kernel in the "
                                 f"compiled program")
        return exe(*args)

    def close(name, got, want, tol):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        err = float(np.max(np.abs(got - want)))
        if not err <= tol:
            raise AssertionError(f"{name}: max|kernel - oracle| = {err:.3e} "
                                 f"> {tol:.0e}")
        return err

    def tree(*a):
        return KT.tree_attention(*a, interpret=interpret)

    def paged(*a):
        return KT.paged_tree_attention(*a, interpret=interpret)

    def paged_cache(*a):
        return KT.paged_cache_attention(*a, interpret=interpret)

    def sparse_partial(*a):
        return KS.sparse_tree_attention_partial(*a, interpret=interpret)

    def sparse(*a):
        return KS.sparse_tree_attention(*a, interpret=interpret)

    errs = {}
    with jax.default_matmul_precision("highest"):
        dense_args = (q, ck, cv, kn, vn, key_pos, q_pos, lo, mask)
        errs["tree_attention"] = close(
            "tree_attention", compiled(tree, *dense_args),
            KR.tree_attention_ref(*dense_args), 2e-2)
        walk = (pkey, pq_pos, lo)
        for label, pk, pv, a, b, ra, rb in (
                ("bf16", pool_k, pool_v, ones, ones, None, None),
                ("int8", qk, qv, sk, sv, sk, sv)):
            args = (q, pk, pv, a, b, kn, vn, table) + walk + (mask,)
            errs[f"paged_tree_attention[{label}]"] = close(
                f"paged_tree_attention[{label}]", compiled(paged, *args),
                KR.paged_tree_attention_ref(q, pk, pv, ra, rb, kn, vn,
                                            table, *walk, mask), 2e-2)
        # the split verify path: cache half + tree half, merged by Eq. 1
        cache_part = compiled(paged_cache, q, qk, qv, sk, sv, table, *walk)
        tree_part = compiled(sparse_partial, q, kn, vn, mask)
        ref_cache = KR.paged_cache_attention_ref(q, qk, qv, sk, sv, table,
                                                 *walk)
        ref_tree = KR.sparse_tree_attention_partial_ref(q, kn, vn, mask)
        errs["paged_cache_attention"] = close(
            "paged_cache_attention", cm.merge_partials([cache_part,
                                                        ref_tree]),
            cm.merge_partials([ref_cache, ref_tree]), 2e-2)
        errs["sparse_tree_attention_partial"] = close(
            "sparse_tree_attention_partial",
            cm.merge_partials([cache_part, tree_part]),
            cm.merge_partials([ref_cache, ref_tree]), 2e-2)
        errs["sparse_tree_attention"] = close(
            "sparse_tree_attention", compiled(sparse, q, kn, vn, mask),
            KR.sparse_tree_ref(q, kn, vn, mask), 3e-2)
    return ", ".join(f"{k} {v:.1e}" for k, v in errs.items())


# --------------------------------------------------------------------------
# (c) lossless check
# --------------------------------------------------------------------------
def lossless(arch=ARCH, *, n_prompts=4, prompt_len=16, tokens=24, width=8):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.speculative import tree as T
    from repro.core.speculative.medusa import init_medusa
    from repro.data.pipeline import MarkovDataset
    from repro.models.api import get_model
    from repro.runtime.cache import paginate_cache
    from repro.runtime.engine import BatchEngine, SpeculativeEngine

    cfg = dataclasses.replace(get_config(arch), dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    heads = init_medusa(cfg, jax.random.PRNGKey(1))
    data = MarkovDataset(cfg.vocab_size, seed=1)
    prompts = data.sample(n_prompts, prompt_len, seed=7)[:, :-1].astype(
        np.int32)
    batch = {"tokens": jnp.asarray(prompts)}
    spec = T.build_tree(T.default_accs(cfg.medusa_heads, cfg.medusa_top_k),
                        width)

    with jax.default_matmul_precision("highest"):
        seq = BatchEngine(model, params, max_len=prompt_len + tokens,
                          backend="ref")
        want, _ = seq.generate(batch, tokens)
        del seq
        eng = SpeculativeEngine(model, heads, params, spec,
                                max_len=prompt_len + tokens + spec.max_depth,
                                paged=True, backend="pallas")
        got, st = eng.generate(batch, tokens)
        del eng
        notes = []
        for b in range(n_prompts):
            diff = np.flatnonzero(got[b] != want[b])
            if not diff.size:
                continue
            j = int(diff[0])
            ctx = np.concatenate([prompts[b], want[b, :j]])[None]
            logits, _, _ = jax.jit(lambda p, t: model.prefill(
                p, {"tokens": t}, return_cache=False, last_logits=True))(
                    params, jnp.asarray(ctx))
            top2 = np.sort(np.asarray(logits[0, -1], np.float32))[-2:]
            gap = float(top2[1] - top2[0])
            notes.append(f"row {b} diverges at token {j}, top-2 gap "
                         f"{gap:.2e}")
            if not gap < TIE_GAP:
                raise AssertionError(
                    f"greedy Ghidorah != greedy sequential: {notes[-1]} "
                    f"(a tie needs a gap under {TIE_GAP:.0e})")

        # verify logits on a paged cache: Pallas kernels vs reference
        tree = T.Tree.from_spec(spec)
        _, _, cache = jax.jit(lambda p, b: model.prefill(
            p, b, max_len=prompt_len))(params, batch)
        maxp = -(-(prompt_len + width) // 16)
        tables = jnp.arange(n_prompts * maxp, dtype=jnp.int32).reshape(
            n_prompts, maxp)
        paged = paginate_cache(cache, tables, page_size=16,
                               n_pages=n_prompts * maxp)
        rng = np.random.default_rng(3)
        tree_tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (n_prompts, spec.width)),
            jnp.int32)

        def verify(backend):
            def f(p, c, t):
                return model.verify(p, c, t, tree, backend=backend)[0]
            return jax.jit(f)(params, paged, tree_tokens)

        err = float(jnp.max(jnp.abs(verify("pallas") - verify("ref"))))
        if not err <= LOGIT_TOL:
            raise AssertionError(f"Pallas verify logits differ from the "
                                 f"reference by {err:.3e} > {LOGIT_TOL:.0e}")
    return (f"{n_prompts}x{tokens} greedy tokens "
            f"{'equal' if not notes else 'equal up to ties'} "
            f"({'; '.join(notes) or 'no divergence'}), acceptance length "
            f"{st['acceptance_length']:.2f}; verify logits max|pallas - ref| "
            f"{err:.2e} (tolerance {LOGIT_TOL:.0e})")


# --------------------------------------------------------------------------
# (d) served path
# --------------------------------------------------------------------------
SERVE_ARMS = (
    ("sequential", ["--mode", "sequential"]),
    ("ghidorah paged", ["--mode", "ghidorah", "--width", "8", "--paged"]),
    ("ghidorah paged int8", ["--mode", "ghidorah", "--width", "8",
                             "--paged", "--kv-dtype", "int8"]),
    ("ghidorah sparse tree kernel", ["--mode", "ghidorah", "--width", "8",
                                     "--paged", "--tree-kernel", "sparse"]),
    ("ghidorah hcmp overlap", ["--mode", "ghidorah", "--width", "8",
                               "--paged", "--hcmp", "overlap"]),
    # width 0: on a TPU, ARCA times each candidate width's compiled step
    ("ghidorah measured width", ["--mode", "ghidorah", "--width", "0",
                                 "--paged"]),
)


def served(arch=ARCH, *, requests=8, tokens=16, prompt_len=16, batch=4):
    """Each arm through ``serve.main``; every request must end DONE with its
    full budget (serve itself fails on undrained pages or a broken gate)."""
    import gc

    from repro.launch import serve

    common = ["--arch", arch, "--arrivals", "poisson", "--rate", "50",
              "--requests", str(requests), "--batch", str(batch),
              "--tokens", str(tokens), "--prompt-len", str(prompt_len),
              "--chunk", "8"]
    lines = []
    for label, arm in SERVE_ARMS:
        t0 = time.perf_counter()
        results, stats = serve.main(common + arm)
        wall = time.perf_counter() - t0
        short = [r.req_id for r in results if r.n_emitted != tokens]
        if len(results) != requests or short:
            raise AssertionError(f"{label}: {len(results)} results, short "
                                 f"budgets on requests {short}")
        lines.append(f"{label}: {stats['emitted_total']} tokens, acceptance "
                     f"length {stats['acceptance_length']:.2f}, makespan "
                     f"{stats['makespan_s']:.2f}s, wall {wall:.1f}s")
        print(f"[chip_smoke]   {lines[-1]}", flush=True)
        del results
        gc.collect()              # the arm's engine and pool leave the chip
    return f"{len(SERVE_ARMS)} arms x {requests} requests served"


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n")[0]).parse_args(
        argv)
    try:
        sys.path.insert(0, str(ROOT / "src"))
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] FAIL: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    print(f"[chip_smoke] compile cache: {use_compile_cache()}", flush=True)
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        rep = run_phase("(a) device report", device_report, clock)
        run_phase("(b) kernel parity", kernel_parity, clock)
        run_phase("(c) lossless check", lossless, clock)
        run_phase("(d) served path", served, clock)
    except PhaseFailed as e:
        print(f"[chip_smoke] FAIL in {e}", file=sys.stderr)
        return 1
    device = {k: rep[k] for k in ("platform", "kind", "count")}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
