"""reprolint (src/repro/analysis): per-rule trigger + near-miss
fixtures, the suppression and baseline machinery, SchedulableEngine
conformance, and — the gate — a clean run over the real ``src/`` tree.

Each rule gets one minimal fixture that MUST fire and one near-miss
that must NOT: the near-misses pin the rules' precision (a linter that
cries wolf gets suppressed wholesale and enforces nothing).
"""
import textwrap
from pathlib import Path

from repro.analysis.core import (Finding, lint_paths, load_baseline,
                                 write_baseline)
from repro.analysis.lint import main as lint_main

SRC = Path(__file__).resolve().parent.parent / "src"


def _lint(tmp_path, files, rules=None):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint_paths([tmp_path], rules=rules)


# ---------------------------------------------------------------------------
# R1 jit purity
# ---------------------------------------------------------------------------

def test_r1_flags_host_clock_reachable_from_jit(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import time
        import jax

        def helper(x):
            t = time.time()
            return x * t

        @jax.jit
        def step(x):
            return helper(x)
        """}, rules=["R1"])
    assert [f.rule for f in found] == ["R1"]
    assert "time.time" in found[0].message and found[0].line == 5


def test_r1_near_miss_unreachable_host_clock(tmp_path):
    # identical helper, but nothing jits it: host clocks are fine there
    found = _lint(tmp_path, {"mod.py": """\
        import time

        def helper(x):
            t = time.time()
            return x * t

        def step(x):
            return helper(x)
        """}, rules=["R1"])
    assert found == []


def test_r1_mutable_default_and_coercion(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        @jax.jit
        def step(x, acc=[]):
            return x + float(x)
        """}, rules=["R1"])
    msgs = " | ".join(f.message for f in found)
    assert "mutable default" in msgs and "float(x)" in msgs


# ---------------------------------------------------------------------------
# R2 donation discipline
# ---------------------------------------------------------------------------

def test_r2_flags_undonated_state_carry(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def update(state, x):
            return state + x

        step = jax.jit(update)
        """}, rules=["R2"])
    assert [f.rule for f in found] == ["R2"]
    assert "donate_argnums" in found[0].message and found[0].line == 6


def test_r2_near_miss_donated_carry(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def update(state, x):
            return state + x

        step = jax.jit(update, donate_argnums=(0,))
        """}, rules=["R2"])
    assert found == []


def test_r2_read_after_donate_vs_rebound_carry(tmp_path):
    bad = _lint(tmp_path, {"bad.py": """\
        import jax

        def update(state, x):
            return state + x

        def run(state, xs):
            step = jax.jit(update, donate_argnums=(0,))
            out = step(state, xs)
            return out + state
        """}, rules=["R2"])
    assert any("read after being donated" in f.message for f in bad)
    good = _lint(tmp_path / "g", {"good.py": """\
        import jax

        def update(state, x):
            return state + x

        def run(state, xs):
            step = jax.jit(update, donate_argnums=(0,))
            state = step(state, xs)
            return state
        """}, rules=["R2"])
    assert good == []


# ---------------------------------------------------------------------------
# R3 host-sync discipline
# ---------------------------------------------------------------------------

def test_r3_flags_sync_in_runtime_hot_path(tmp_path):
    found = _lint(tmp_path, {"runtime/hot.py": """\
        import numpy as np

        class E:
            def sched_step(self, x):
                return np.asarray(x)
        """}, rules=["R3"])
    assert [f.rule for f in found] == ["R3"]
    assert "np.asarray" in found[0].message and found[0].line == 5


def test_r3_near_miss_cold_function_and_benchmark(tmp_path):
    # same sync outside a hot function, and a benchmark's
    # block_until_ready (the measurement itself): both clean
    found = _lint(tmp_path, {
        "runtime/cold.py": """\
            import numpy as np

            class E:
                def snapshot(self, x):
                    return np.asarray(x)
            """,
        "benchmarks/bench_decode.py": """\
            import jax
            import time

            def run(f, x):
                t0 = time.time()
                jax.block_until_ready(f(x))
                return time.time() - t0
            """}, rules=["R3"])
    assert found == []


def test_r3_flags_wall_clock_outside_benchmarks(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import time

        def measure(f):
            t0 = time.time()
            f()
            return time.time() - t0
        """}, rules=["R3"])
    assert len(found) == 2
    assert all("perf_counter" in f.message for f in found)


# ---------------------------------------------------------------------------
# R4 lock + thread-ownership discipline
# ---------------------------------------------------------------------------

def test_r4_flags_off_lock_read_of_guarded_attr(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                with self._lock:
                    self.count += 1

            def peek(self):
                return self.count
        """}, rules=["R4"])
    assert [f.rule for f in found] == ["R4"]
    assert "off-lock" in found[0].message and found[0].line == 13


def test_r4_near_miss_locked_read(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                with self._lock:
                    self.count += 1

            def peek(self):
                with self._lock:
                    return self.count
        """}, rules=["R4"])
    assert found == []


def test_r4_flags_scheduler_reached_off_worker(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import threading

        class Srv:
            def __init__(self, scheduler):
                self.scheduler = scheduler
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._run)
                self._thread.start()

            def _run(self):
                self.scheduler.boundary()

            def peek(self):
                return self.scheduler.load
        """}, rules=["R4"])
    assert [f.rule for f in found] == ["R4"]
    assert "worker-owned" in found[0].message and found[0].line == 16


# ---------------------------------------------------------------------------
# R5 pytree completeness
# ---------------------------------------------------------------------------

def test_r5_flags_missing_field(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import dataclasses
        from functools import partial
        import jax

        @partial(jax.tree_util.register_dataclass,
                 data_fields=["a"], meta_fields=[])
        @dataclasses.dataclass
        class S:
            a: int
            b: int
        """}, rules=["R5"])
    assert [f.rule for f in found] == ["R5"]
    assert "`b`" in found[0].message


def test_r5_near_miss_complete_registration(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import dataclasses
        from functools import partial
        import jax

        @partial(jax.tree_util.register_dataclass,
                 data_fields=["a"], meta_fields=["b"])
        @dataclasses.dataclass
        class S:
            a: int
            b: int
        """}, rules=["R5"])
    assert found == []


def test_r5_flags_dropped_flatten_field(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        class P:
            def __init__(self, a, b):
                self.a = a
                self.b = b

        jax.tree_util.register_pytree_node(
            P, lambda p: ((p.a,), None), lambda aux, kids: P(kids[0], 0))
        """}, rules=["R5"])
    assert any("never reads field `b`" in f.message for f in found)


# ---------------------------------------------------------------------------
# R6 slot-protocol conformance
# ---------------------------------------------------------------------------

_R6_SCHED = """\
    def drive(eng):
        eng.sched_step()
        eng.sched_reset()
        if hasattr(eng, "sched_abort"):
            eng.sched_abort(0)
    """


def test_r6_flags_partial_engine(tmp_path):
    found = _lint(tmp_path, {
        "runtime/scheduler.py": _R6_SCHED,
        "runtime/engine.py": """\
            class ToyEngine:
                def sched_step(self):
                    return 0
            """}, rules=["R6"])
    assert [f.rule for f in found] == ["R6"]
    assert "sched_reset" in found[0].message
    # the hasattr-probed slot is an optional extension, never required
    assert "sched_abort" not in found[0].message.split("optional")[0]


def test_r6_near_miss_full_engine_without_optional(tmp_path):
    found = _lint(tmp_path, {
        "runtime/scheduler.py": _R6_SCHED,
        "runtime/engine.py": """\
            class ToyEngine:
                def sched_step(self):
                    return 0

                def sched_reset(self):
                    return 0
            """}, rules=["R6"])
    assert found == []


def test_r6_flags_protocol_lagging_scheduler(tmp_path):
    found = _lint(tmp_path, {
        "runtime/scheduler.py": _R6_SCHED,
        "runtime/engine.py": """\
            from typing import Protocol

            class SchedulableEngine(Protocol):
                def sched_step(self):
                    ...
            """}, rules=["R6"])
    assert any("does not declare" in f.message and "sched_reset"
               in f.message for f in found)


def test_engine_aliases_conform_to_protocol():
    """Both engine aliases satisfy the typed contract at runtime, not
    just under R6's static scrape."""
    from repro.runtime.engine import (BatchEngine, DecodeEngine,
                                      SchedulableEngine, SpeculativeEngine)
    for cls in (DecodeEngine, BatchEngine, SpeculativeEngine):
        assert issubclass(cls, SchedulableEngine), cls.__name__


# ---------------------------------------------------------------------------
# R7 retrace / compile-cache audit
# ---------------------------------------------------------------------------

def test_r7_flags_jit_built_in_hot_path_and_loop(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def f(x):
            return x

        class Eng:
            def generate(self, x):
                return jax.jit(f)(x)

        def warm(xs):
            for x in xs:
                y = jax.jit(f)(x)
            return y
        """}, rules=["R7"])
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2
    assert "hot path" in msgs and "inside a loop" in msgs


def test_r7_near_miss_memoised_and_init_construction(tmp_path):
    # the two sanctioned patterns: build once in __init__, or memoise
    # per static key — neither defeats the compile cache
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def f(x):
            return x

        class Eng:
            def __init__(self):
                self._step = jax.jit(f)
                self._memo = {}

            def generate(self, x):
                if "f" not in self._memo:
                    self._memo["f"] = jax.jit(f)
                return self._memo["f"](self._step(x))
        """}, rules=["R7"])
    assert found == []


def test_r7_flags_fresh_lambda_static_arg(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def apply(x, fn):
            return fn(x)

        step = jax.jit(apply, static_argnums=(1,))

        def run(x):
            return step(x, lambda y: y + 1)
        """}, rules=["R7"])
    assert [f.rule for f in found] == ["R7"]
    assert "lambda" in found[0].message and "static" in found[0].message


def test_r7_near_miss_stable_static_arg(tmp_path):
    # a module-level def is one object for the process lifetime: the
    # identity-hash static key is stable, so the cache hits
    found = _lint(tmp_path, {"mod.py": """\
        import jax

        def apply(x, fn):
            return fn(x)

        def bump(y):
            return y + 1

        step = jax.jit(apply, static_argnums=(1,))

        def run(x):
            return step(x, bump)
        """}, rules=["R7"])
    assert found == []


def test_r7_flags_scalar_vs_array_skew_across_call_sites(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, eos):
            return x + eos

        def from_scheduler(x):
            return step(x, 7)

        def from_generate(x):
            return step(x, jnp.asarray(7))
        """}, rules=["R7"])
    assert [f.rule for f in found] == ["R7"]
    assert "eos" in found[0].message and "retraces" in found[0].message


def test_r7_near_miss_consistent_avals(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, eos):
            return x + eos

        def from_scheduler(x):
            return step(x, jnp.asarray(7))

        def from_generate(x):
            return step(x, jnp.asarray(9))
        """}, rules=["R7"])
    assert found == []


# ---------------------------------------------------------------------------
# R8 kernel bounds verifier
# ---------------------------------------------------------------------------

_REAL_TREE = SRC / "repro/kernels/tree_attention.py"


def test_r8_flags_unclamped_index_map(tmp_path):
    """Drop the tail-block clamp from the REAL kernel's KV index maps:
    the verifier must prove the resulting block starts run off the end
    of the operand, for concrete (config, grid point) witnesses."""
    src = _REAL_TREE.read_text()
    assert "jnp.minimum(i, _n - 1)" in src    # the clamp under mutation
    found = _lint(tmp_path, {
        "kernels/tree_attention.py":
            src.replace("jnp.minimum(i, _n - 1)", "i")}, rules=["R8"])
    assert found and all(f.rule == "R8" for f in found)
    assert any("out of bounds" in f.message and "grid point" in f.message
               for f in found)


def test_r8_flags_block_that_breaks_tpu_tiling(tmp_path):
    """Fetch one kv head per block, as the kernels once did: the block's
    second-minor dim (1) is neither the operand's Hkv nor a multiple of 8,
    which Mosaic refuses and interpret mode never notices."""
    src = _REAL_TREE.read_text()
    assert "pl.BlockSpec((1, bs, hg, hd)," in src
    found = _lint(tmp_path, {
        "kernels/tree_attention.py":
            src.replace("pl.BlockSpec((1, bs, hg, hd),",
                        "pl.BlockSpec((1, bs, 1, hd),")}, rules=["R8"])
    assert found and all(f.rule == "R8" for f in found)
    assert any("tiling rule" in f.message for f in found)


def test_r8_near_miss_real_kernel_verifies(tmp_path):
    # the committed kernel, verbatim: every index map proves in-bounds,
    # every out_spec tiles exactly once, for the whole config matrix
    found = _lint(tmp_path, {
        "kernels/tree_attention.py": _REAL_TREE.read_text()},
        rules=["R8"])
    assert found == []


# ---------------------------------------------------------------------------
# R9 boundary-protocol conformance
# ---------------------------------------------------------------------------

def test_r9_flags_admit_before_sweep_and_undrained_fail_all(tmp_path):
    found = _lint(tmp_path, {"runtime/scheduler.py": """\
        class ContinuousScheduler:
            def submit(self, req):
                self._pending.append(req)

            def abort(self, req_id):
                self._aborts[req_id] = 1

            def boundary(self):
                req = self.policy.pick(self._pending)
                self._apply_aborts()
                return req

            def fail_all(self):
                self._aborts = {}
        """}, rules=["R9"])
    msgs = " | ".join(f.message for f in found)
    assert "BEFORE the abort sweep" in msgs
    assert "does not drain self._pending" in msgs
    # the model exploration itself is clean: only the two static
    # protocol-order findings fire
    assert len(found) == 2


def test_r9_near_miss_correct_protocol_order(tmp_path):
    found = _lint(tmp_path, {"runtime/scheduler.py": """\
        class ContinuousScheduler:
            def submit(self, req):
                self._pending.append(req)

            def abort(self, req_id):
                self._aborts[req_id] = 1

            def boundary(self):
                self._apply_aborts()
                req = self.policy.pick(self._pending)
                return req

            def fail_all(self):
                self._pending = []
                self._aborts = {}
        """}, rules=["R9"])
    assert found == []


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI
# ---------------------------------------------------------------------------

def test_inline_and_file_suppressions(tmp_path):
    found = _lint(tmp_path, {"mod.py": """\
        import time

        def measure(f):
            t0 = time.time()  # reprolint: disable=R3 (absolute timestamp)
            # reprolint: disable=R3 — line-above form
            t1 = time.time()
            f()
            return t1 - t0
        """}, rules=["R3"])
    assert found == []
    found = _lint(tmp_path / "f", {"mod.py": """\
        # reprolint: disable-file=R3
        import time

        def measure(f):
            f()
            return time.time()
        """}, rules=["R3"])
    assert found == []


def test_suppression_is_rule_specific(tmp_path):
    # a R4 suppression must not silence R3 on the same line
    found = _lint(tmp_path, {"mod.py": """\
        import time

        def measure():
            return time.time()  # reprolint: disable=R4
        """}, rules=["R3"])
    assert [f.rule for f in found] == ["R3"]


def test_baseline_roundtrip_and_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text("import time\n\n\ndef f():\n    return time.time()\n")
    baseline = tmp_path / "baseline.txt"
    # fresh finding: exit 1, rendered as path:line RULE message
    assert lint_main([str(tmp_path), "--rules", "R3",
                      "--baseline", str(baseline)]) == 1
    out = capsys.readouterr().out
    assert "mod.py:5 R3" in out
    # grandfather it, then the same tree is clean
    assert lint_main([str(tmp_path), "--rules", "R3",
                      "--baseline", str(baseline),
                      "--write-baseline"]) == 0
    assert lint_main([str(tmp_path), "--rules", "R3",
                      "--baseline", str(baseline)]) == 0
    keys = load_baseline(baseline)
    assert len(keys) == 1 and next(iter(keys)).startswith("mod.py::R3::")
    # fixing the finding leaves a stale entry but stays exit 0
    bad.write_text("import time\n\n\ndef f():\n    return 0\n")
    assert lint_main([str(tmp_path), "--rules", "R3",
                      "--baseline", str(baseline)]) == 0


def test_github_format_emits_workflow_annotations(tmp_path, capsys):
    """--format github adds an ::error workflow command per fresh
    finding (on top of the human rendering) so CI annotates the PR."""
    (tmp_path / "mod.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    assert lint_main([str(tmp_path), "--rules", "R3",
                      "--baseline", str(tmp_path / "b.txt"),
                      "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "mod.py:5 R3" in out                      # human line kept
    assert "::error file=mod.py,line=5,title=reprolint R3::R3: " in out


def test_finding_key_is_line_number_free(tmp_path):
    f = Finding(path="a.py", line=7, rule="R1", message="m")
    assert f.key == "a.py::R1::m" and "7" not in f.key
    write_baseline(tmp_path / "b.txt", [f])
    assert load_baseline(tmp_path / "b.txt") == {"a.py::R1::m"}


# ---------------------------------------------------------------------------
# the gate: the real tree is clean
# ---------------------------------------------------------------------------

def test_src_tree_is_clean():
    """Every finding in src/ is fixed or carries a reasoned inline
    suppression; the committed baseline stays empty.  A regression here
    means new code broke one of the nine invariants — fix it or suppress
    it with a reason, don't baseline it."""
    findings = lint_paths([SRC])
    assert findings == [], "\n".join(f.render() for f in findings)
    assert load_baseline(SRC / "repro/analysis/baseline.txt") == set()
