"""R8 engine — concrete-evaluation bounds/coverage verification of the
Pallas ``BlockSpec`` index maps in ``kernels/tree_attention.py`` and
``kernels/sparse_tree.py``.

``BlockSpec`` index maps are *pure Python* lambdas: they can be compiled
and executed without jax, over every point of the concrete grid, for a
matrix of representative shape configs.  For each (wrapper, config) this
module proves:

* **bounds** — every in/out block index is a well-formed tuple of the
  right arity with ``0 <= idx[d]`` and
  ``idx[d]*block[d] + block[d] <= operand_shape[d]`` at *every* grid
  point (the DMA engine fetches the block whether or not the kernel
  branch reads it, so a clamp bug is a real OOB fetch);
* **coverage** — the out_specs tile the output exactly once: block
  shape divides the output shape, every tile is produced, distinct grid
  points that revisit one tile form a contiguous run in lexicographic
  grid order (the sequential minor-most axis on TPU — a non-contiguous
  revisit would clobber the online-softmax accumulator);
* **tiling** — each of a block's two minor dims equals the operand's or
  is a multiple of the (8, 128) TPU tile (the Pallas TPU lowering's
  rule, which interpret mode never checks);
* **page domain** (paged wrapper) — the table-walk can only address
  pages reserved in that sequence's block-table row or the trailing
  trash page ``P - 1``, never another sequence's pages via an
  unclamped ``-1``.

The wrapper's shape arithmetic (``bs``/``pad``/``nblocks``/the table
pre-clamp) is mirrored here per wrapper name; an index map that uses a
name the harness doesn't model, or a pallas wrapper with no config
entry, is itself a finding — the harness must grow with the kernels.

Verified domain: ``S >= 1`` (dense) and ``max_pages >= 1`` (paged) —
matching what the engines can construct (a KV cache always has at
least one slot / one logical page).

Everything here is stdlib-only so the lint CI job runs without jax.
"""
from __future__ import annotations

import ast
import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.callgraph import dotted


# --------------------------------------------------------------------------
# tiny eval environment: index maps call jnp.minimum/maximum and index
# the scalar-prefetch table; on concrete ints both are plain Python
# --------------------------------------------------------------------------
class _JnpShim:
    @staticmethod
    def minimum(a, b):
        return min(a, b)

    @staticmethod
    def maximum(a, b):
        return max(a, b)

    @staticmethod
    def where(c, a, b):
        return a if c else b


class _Table:
    """Scalar-prefetch block table: supports ``t[b, i]``."""

    def __init__(self, rows: Sequence[Sequence[int]]):
        self.rows = [list(r) for r in rows]

    def __getitem__(self, key):
        b, i = key
        return self.rows[b][i]


@dataclasses.dataclass
class Config:
    """One concrete shape configuration for a wrapper."""
    desc: str
    env: Dict[str, int]                 # wrapper-derived scalars
    operands: List[Tuple[int, ...]]     # shapes, same order as in_specs
    table: Optional[List[List[int]]] = None   # raw block table (-1 free)
    pool_operands: Tuple[int, ...] = ()       # in_spec indices into pool


@dataclasses.dataclass
class KernelSpec:
    """Extracted pallas_call structure of one wrapper."""
    name: str
    line: int
    grid: ast.expr
    in_specs: List[Tuple[ast.expr, ast.expr, int]]   # (shape, map, line)
    out_spec: Tuple[ast.expr, ast.expr, int]
    out_shape: ast.expr
    n_prefetch: int


# --------------------------------------------------------------------------
# config matrix — dense + paged + sparse, page-size / W / depth sweeps
# --------------------------------------------------------------------------
def _heads(Hq, Hkv):
    """Mirror of ``tree_attention._head_group``: kv heads per grid step."""
    hg = 8 if Hkv % 8 == 0 else Hkv
    return dict(G=Hq // Hkv, hg=hg, nh=Hkv // hg)


def _dense_cfg(B, W, Hq, Hkv, hd, S, block_s) -> Config:
    h = _heads(Hq, Hkv)
    G = h["G"]
    bs = min(block_s, max(S, 1))
    pad = (-S) % bs
    nblocks = (S + pad) // bs
    Sp = S + pad
    env = dict(B=B, W=W, Hq=Hq, Hkv=Hkv, hd=hd, S=S, bs=bs, pad=pad,
               nblocks=nblocks, block_s=block_s, **h)
    ops = [(B, Hkv, G * W, hd), (B, Sp, Hkv, hd), (B, Sp, Hkv, hd),
           (B, W, Hkv, hd), (B, W, Hkv, hd), (B, nblocks, 1, bs),
           (B, G * W, 1), (B, G * W, 1), (G * W, W)]
    return Config(
        desc=f"dense B={B} W={W} Hq={Hq} Hkv={Hkv} hd={hd} S={S} "
             f"block_s={block_s} (bs={bs} pad={pad} nblocks={nblocks})",
        env=env, operands=ops)


def _paged_cfg(B, W, Hq, Hkv, hd, ps, P, tables) -> Config:
    # operand order mirrors the wrapper: q, pool_k, pool_v, scale_k,
    # scale_v, k_new, v_new, key_pos, q_pos, lo, tree_mask.  The
    # (P, Hkv, 1) dequant scales walk the SAME table-driven index map as
    # the pools, so they join the page-domain check (a scale fetched from
    # another sequence's page would dequantize with the wrong amax).
    h = _heads(Hq, Hkv)
    G = h["G"]
    maxp = len(tables[0])
    env = dict(B=B, W=W, Hq=Hq, Hkv=Hkv, hd=hd, P=P, ps=ps, maxp=maxp, **h)
    ops = [(B, Hkv, G * W, hd), (P, ps, Hkv, hd), (P, ps, Hkv, hd),
           (P, Hkv, 1), (P, Hkv, 1),
           (B, W, Hkv, hd), (B, W, Hkv, hd), (B, maxp, 1, ps),
           (B, G * W, 1), (B, G * W, 1), (G * W, W)]
    reserved = [sum(1 for v in row if v >= 0) for row in tables]
    return Config(
        desc=f"paged B={B} W={W} Hq={Hq} Hkv={Hkv} hd={hd} ps={ps} "
             f"pages={P} maxp={maxp} reserved={reserved}",
        env=env, operands=ops, table=tables, pool_operands=(1, 2, 3, 4))


def _paged_cache_cfg(B, W, Hq, Hkv, hd, ps, P, tables) -> Config:
    """``paged_cache_attention`` (split verify path): the paged walk minus
    the tree operands — q, pool_k, pool_v, scale_k, scale_v, key_pos,
    q_pos, lo — with a (B, nh, maxp) grid (no trailing tree block)."""
    h = _heads(Hq, Hkv)
    G = h["G"]
    maxp = len(tables[0])
    env = dict(B=B, W=W, Hq=Hq, Hkv=Hkv, hd=hd, P=P, ps=ps, maxp=maxp, **h)
    ops = [(B, Hkv, G * W, hd), (P, ps, Hkv, hd), (P, ps, Hkv, hd),
           (P, Hkv, 1), (P, Hkv, 1), (B, maxp, 1, ps), (B, G * W, 1),
           (B, G * W, 1)]
    reserved = [sum(1 for v in row if v >= 0) for row in tables]
    return Config(
        desc=f"paged-cache B={B} W={W} Hq={Hq} Hkv={Hkv} hd={hd} ps={ps} "
             f"pages={P} maxp={maxp} reserved={reserved}",
        env=env, operands=ops, table=tables, pool_operands=(1, 2, 3, 4))


def _sparse_cfg(B, W, Hq, Hkv, hd) -> Config:
    h = _heads(Hq, Hkv)
    G = h["G"]
    env = dict(B=B, W=W, Hq=Hq, Hkv=Hkv, hd=hd, **h)
    ops = [(B, Hkv, G * W, hd), (B, W, Hkv, hd), (B, W, Hkv, hd),
           (G * W, W)]
    return Config(desc=f"sparse B={B} W={W} Hq={Hq} Hkv={Hkv} hd={hd}",
                  env=env, operands=ops)


CONFIGS: Dict[str, List[Config]] = {
    "tree_attention": [
        _dense_cfg(2, 4, 4, 2, 8, 16, 8),      # exact block multiple
        _dense_cfg(1, 2, 2, 1, 4, 5, 4),       # padded tail (pad=3)
        _dense_cfg(3, 4, 8, 4, 16, 3, 512),    # S < block_s (bs=S)
        _dense_cfg(2, 8, 8, 2, 8, 64, 16),     # deep tree, 4 KV blocks
        _dense_cfg(1, 4, 4, 4, 8, 1, 512),     # single-slot cache
        _dense_cfg(2, 2, 16, 16, 8, 24, 8),    # two 8-head groups
    ],
    "paged_tree_attention": [
        _paged_cfg(2, 4, 4, 2, 8, 8, 6,
                   [[0, 1, 2, -1], [3, -1, -1, -1]]),
        _paged_cfg(1, 2, 2, 1, 4, 16, 3, [[-1, -1]]),   # 0 reserved
        _paged_cfg(3, 4, 8, 4, 16, 8, 9,
                   [[0, 1, 2, 3, 4, 5], [6, 7, -1, -1, -1, -1],
                    [-1] * 6]),                          # full/partial/0
        _paged_cfg(2, 8, 8, 8, 8, 16, 4, [[0], [2]]),    # maxp=1 edge
        _paged_cfg(2, 2, 16, 16, 8, 4, 5,
                   [[0, 1, -1], [2, -1, -1]]),           # two head groups
    ],
    "paged_cache_attention": [
        _paged_cache_cfg(2, 4, 4, 2, 8, 8, 6,
                         [[0, 1, 2, -1], [3, -1, -1, -1]]),
        _paged_cache_cfg(1, 2, 2, 1, 4, 16, 3, [[-1, -1]]),
        _paged_cache_cfg(3, 4, 8, 4, 16, 8, 9,
                         [[0, 1, 2, 3, 4, 5], [6, 7, -1, -1, -1, -1],
                          [-1] * 6]),
        _paged_cache_cfg(2, 8, 8, 8, 8, 16, 4, [[0], [2]]),
        _paged_cache_cfg(2, 2, 16, 16, 8, 4, 5, [[0, 1, -1], [2, -1, -1]]),
    ],
    "sparse_tree_attention": [
        _sparse_cfg(2, 4, 4, 2, 8),
        _sparse_cfg(1, 2, 2, 2, 4),
        _sparse_cfg(3, 8, 8, 4, 16),
        _sparse_cfg(2, 2, 32, 16, 8),
    ],
    # the W x W tree half of the split verify path: same operands as
    # sparse_tree_attention, packed-(hd + 2) partials output
    "sparse_tree_attention_partial": [
        _sparse_cfg(2, 4, 4, 2, 8),
        _sparse_cfg(1, 2, 2, 2, 4),
        _sparse_cfg(3, 8, 8, 4, 16),
        _sparse_cfg(2, 2, 32, 16, 8),
    ],
}


# --------------------------------------------------------------------------
# extraction
# --------------------------------------------------------------------------
def _local_value(fn_node, name: str) -> Optional[ast.expr]:
    """Last ``name = <expr>`` assignment in the wrapper's own body."""
    found = None
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    found = n.value
    return found


def _deref(fn_node, expr) -> Optional[ast.expr]:
    if isinstance(expr, ast.Name):
        return _local_value(fn_node, expr.id)
    return expr


def _blockspec_parts(call: ast.Call) -> Optional[Tuple[ast.expr, ast.expr]]:
    d = dotted(call.func)
    if d is None or d.split(".")[-1] != "BlockSpec":
        return None
    shape = call.args[0] if len(call.args) > 0 else None
    imap = call.args[1] if len(call.args) > 1 else None
    for k in call.keywords:
        if k.arg in ("block_shape",):
            shape = k.value
        elif k.arg in ("index_map",):
            imap = k.value
    if shape is None or imap is None:
        return None
    return shape, imap


def extract_kernel_spec(fn_node) -> Tuple[Optional[KernelSpec], List[str]]:
    """Parse the wrapper's pallas_call into a KernelSpec (or reasons)."""
    errors: List[str] = []
    pc = None
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Call) and (dotted(n.func) or "").endswith(
                "pallas_call"):
            pc = n
    if pc is None:
        return None, ["no pallas_call found"]
    kw = {k.arg: k.value for k in pc.keywords}
    grid = _deref(fn_node, kw.get("grid"))
    in_specs = _deref(fn_node, kw.get("in_specs"))
    out_spec = _deref(fn_node, kw.get("out_specs"))
    out_shape = _deref(fn_node, kw.get("out_shape"))
    n_prefetch = 0
    gs = _deref(fn_node, kw.get("grid_spec"))
    if gs is not None:
        if not (isinstance(gs, ast.Call) and (dotted(gs.func) or "")
                .endswith("PrefetchScalarGridSpec")):
            return None, ["grid_spec is not a PrefetchScalarGridSpec call"]
        gkw = {k.arg: k.value for k in gs.keywords}
        grid = _deref(fn_node, gkw.get("grid"))
        in_specs = _deref(fn_node, gkw.get("in_specs"))
        out_spec = _deref(fn_node, gkw.get("out_specs"))
        np_ = gkw.get("num_scalar_prefetch")
        if isinstance(np_, ast.Constant) and isinstance(np_.value, int):
            n_prefetch = np_.value
        else:
            errors.append("num_scalar_prefetch is not an int literal")
    if grid is None:
        errors.append("no grid expression")
    if not isinstance(in_specs, ast.List):
        errors.append("in_specs is not a literal list of BlockSpecs")
    if out_shape is not None and isinstance(out_shape, ast.Call) and \
            (dotted(out_shape.func) or "").endswith("ShapeDtypeStruct"):
        out_shape = out_shape.args[0] if out_shape.args else None
    if out_shape is None:
        errors.append("no out_shape ShapeDtypeStruct")
    parsed_in: List[Tuple[ast.expr, ast.expr, int]] = []
    if isinstance(in_specs, ast.List):
        for e in in_specs.elts:
            parts = _blockspec_parts(e) if isinstance(e, ast.Call) else None
            if parts is None:
                errors.append(f"in_spec at line {e.lineno} is not a "
                              f"BlockSpec(shape, index_map) call")
            else:
                parsed_in.append((parts[0], parts[1], e.lineno))
    parsed_out = None
    if isinstance(out_spec, ast.Call):
        parts = _blockspec_parts(out_spec)
        if parts is not None:
            parsed_out = (parts[0], parts[1], out_spec.lineno)
    if parsed_out is None:
        errors.append("out_specs is not a BlockSpec(shape, index_map) call")
    if errors:
        return None, errors
    return KernelSpec(name=fn_node.name, line=fn_node.lineno, grid=grid,
                      in_specs=parsed_in, out_spec=parsed_out,
                      out_shape=out_shape, n_prefetch=n_prefetch), []


# --------------------------------------------------------------------------
# verification
# --------------------------------------------------------------------------
def _evaluate(expr, env: Dict) -> object:
    node = ast.Expression(body=expr)
    ast.fix_missing_locations(node)
    code = compile(node, "<kernelbounds>", "eval")
    genv = {"__builtins__": {}, "jnp": _JnpShim}
    genv.update(env)
    return eval(code, genv)          # noqa: S307 — our own parsed source


def _untiled_dims(blk: Tuple, opshape: Tuple) -> List[int]:
    """Minor block dims that Mosaic refuses: neither the operand's full
    extent nor a multiple of the (8, 128) tile."""
    bad = []
    for d, tile in zip(range(len(blk) - 2, len(blk)), (8, 128)):
        if d >= 0 and blk[d] != opshape[d] and blk[d] % tile:
            bad.append(d)
    return bad


def _as_tuple(v) -> Tuple:
    return tuple(v) if isinstance(v, tuple) else (v,)


def check_spec(spec: KernelSpec, cfg: Config) -> List[Tuple[int, str]]:
    """All violations of one config against one extracted spec."""
    errs: List[Tuple[int, str]] = []

    def ev(expr, line, what):
        try:
            return _evaluate(expr, cfg.env)
        except NameError as e:
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} uses "
                         f"a name the bounds harness does not model "
                         f"({e}) — extend repro/analysis/kernelbounds.py"))
        except Exception as e:                      # noqa: BLE001
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                         f"failed to evaluate: {e!r}"))
        return None

    grid = ev(spec.grid, spec.line, "grid")
    if grid is None:
        return errs
    grid = _as_tuple(grid)
    if not all(isinstance(g, int) and g >= 1 for g in grid):
        errs.append((spec.line, f"`{spec.name}` [{cfg.desc}]: grid "
                     f"evaluated to {grid!r}, expected positive ints"))
        return errs
    if len(cfg.operands) != len(spec.in_specs):
        errs.append((spec.line,
                     f"`{spec.name}` [{cfg.desc}]: {len(spec.in_specs)} "
                     f"in_specs but the harness models "
                     f"{len(cfg.operands)} operands — extend "
                     f"repro/analysis/kernelbounds.py"))
        return errs
    extra: Tuple = ()
    allowed = None
    if spec.n_prefetch:
        if spec.n_prefetch != 1 or cfg.table is None:
            errs.append((spec.line, f"`{spec.name}` [{cfg.desc}]: "
                         f"num_scalar_prefetch={spec.n_prefetch} not "
                         f"modelled (harness supports exactly one "
                         f"block table)"))
            return errs
        P = cfg.env["P"]
        clamped = [[P - 1 if v < 0 else v for v in row]
                   for row in cfg.table]
        extra = (_Table(clamped),)
        allowed = [{v for v in row if v >= 0} | {P - 1}
                   for row in cfg.table]

    points = list(itertools.product(*(range(g) for g in grid)))

    def run_spec(shape_e, map_e, line, opshape, what, pool_i=None):
        """Evaluate one BlockSpec over the grid; returns the per-point
        block indices (or None after reporting)."""
        blk = ev(shape_e, line, f"{what} block shape")
        imap = ev(map_e, line, f"{what} index map")
        if blk is None or imap is None:
            return None
        blk = _as_tuple(blk)
        if len(blk) != len(opshape):
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                         f"block shape {blk} has rank {len(blk)} but "
                         f"the operand is rank {len(opshape)} "
                         f"{opshape}"))
            return None
        bad = _untiled_dims(blk, opshape)
        if bad:
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                         f"block {blk} over operand {opshape} breaks "
                         f"the TPU tiling rule in dims {bad}: each of "
                         f"the two minor block dims must equal the "
                         f"operand's or be a multiple of (8, 128)"))
            return None
        if not callable(imap):
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                         f"index map is not callable"))
            return None
        out = []
        for pt in points:
            try:
                idx = _as_tuple(imap(*pt, *extra))
            except Exception as e:                  # noqa: BLE001
                errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                             f"index map raised at grid point {pt}: "
                             f"{e!r}"))
                return None
            if len(idx) != len(blk):
                errs.append((line, f"`{spec.name}` [{cfg.desc}]: {what} "
                             f"index map returned {len(idx)} indices "
                             f"for a rank-{len(blk)} block at grid "
                             f"point {pt}"))
                return None
            for d, (i, b, s) in enumerate(zip(idx, blk, opshape)):
                if i < 0 or i * b + b > s:
                    errs.append((line, f"`{spec.name}` [{cfg.desc}]: "
                                 f"{what} block index {idx} at grid "
                                 f"point {pt} is out of bounds in dim "
                                 f"{d} (block {b} x index {i} vs "
                                 f"operand extent {s})"))
                    return None
            if pool_i is not None and allowed is not None:
                b_row = pt[0]
                if idx[0] not in allowed[b_row]:
                    errs.append((line, f"`{spec.name}` [{cfg.desc}]: "
                                 f"{what} addresses physical page "
                                 f"{idx[0]} at grid point {pt}, which "
                                 f"is neither reserved for sequence "
                                 f"{b_row} nor the trash page — the "
                                 f"table walk escapes its page set"))
                    return None
            out.append(idx)
        return out

    for i, (shape_e, map_e, line) in enumerate(spec.in_specs):
        run_spec(shape_e, map_e, line, cfg.operands[i],
                 f"in_spec[{i}]",
                 pool_i=i if i in cfg.pool_operands else None)

    out_shape = ev(spec.out_shape, spec.out_spec[2], "out_shape")
    if out_shape is None:
        return errs
    out_shape = _as_tuple(out_shape)
    shape_e, map_e, line = spec.out_spec
    idxs = run_spec(shape_e, map_e, line, out_shape, "out_spec")
    if idxs is None:
        return errs
    blk = _as_tuple(_evaluate(shape_e, cfg.env))
    bad_div = [d for d in range(len(blk)) if out_shape[d] % blk[d]]
    if bad_div:
        errs.append((line, f"`{spec.name}` [{cfg.desc}]: out block "
                     f"{blk} does not divide output shape {out_shape} "
                     f"in dims {bad_div} — tiles cannot partition the "
                     f"output"))
        return errs
    visits: Dict[Tuple, List[int]] = {}
    for n, idx in enumerate(idxs):
        visits.setdefault(idx, []).append(n)
    want = 1
    for d in range(len(blk)):
        want *= out_shape[d] // blk[d]
    if len(visits) != want:
        errs.append((line, f"`{spec.name}` [{cfg.desc}]: out_specs "
                     f"produce {len(visits)} distinct tiles but the "
                     f"output has {want} — coverage is not exactly-once"
                     f" (missing or duplicated tiles)"))
    for idx, pos in visits.items():
        if max(pos) - min(pos) + 1 != len(pos):
            errs.append((line, f"`{spec.name}` [{cfg.desc}]: output "
                         f"tile {idx} is revisited non-contiguously in "
                         f"grid order (visit steps {pos}) — on TPU "
                         f"only a contiguous minor-axis run may "
                         f"revisit a tile (accumulator semantics)"))
            break
    return errs


def verify_tree(tree: ast.Module) -> List[Tuple[int, str]]:
    """All R8 violations in one kernel module's AST."""
    errs: List[Tuple[int, str]] = []
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        has_pc = any(isinstance(n, ast.Call) and
                     (dotted(n.func) or "").endswith("pallas_call")
                     for n in ast.walk(node))
        if not has_pc:
            continue
        cfgs = CONFIGS.get(node.name)
        if cfgs is None:
            errs.append((node.lineno,
                         f"pallas wrapper `{node.name}` has no "
                         f"bounds-verification config — add a shape "
                         f"matrix entry in "
                         f"repro/analysis/kernelbounds.py"))
            continue
        spec, reasons = extract_kernel_spec(node)
        if spec is None:
            for r in reasons:
                errs.append((node.lineno,
                             f"cannot extract pallas_call structure of "
                             f"`{node.name}` for bounds verification: "
                             f"{r}"))
            continue
        for cfg in cfgs:
            errs.extend(check_spec(spec, cfg))
    return errs
