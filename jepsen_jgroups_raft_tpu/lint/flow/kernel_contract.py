"""Kernel-contract analyzer: static Pallas/launch shape verification.

A mis-sized ``BlockSpec``, a grid that does not tile the output, or a
VMEM-oversized block is today a *runtime* failure — Mosaic rejects the
lowering or XLA OOMs — discovered only after burning paid TPU time.
This analyzer evaluates the shape arithmetic around every
``pl.pallas_call`` **statically**: the enclosing scopes' assignments are
executed by the restricted interpreter (:mod:`.interp`) under sampled
symbol bindings drawn from the file's declared contract, and the
resulting concrete grids/blocks/shapes are checked against the Mosaic
and VMEM rules. Files with no symbols (test fixtures with literal
shapes) evaluate under the single empty binding.

Rules
-----
``kernel-block-divide``
    An out_spec block dim does not divide the declared ``out_shape`` dim.
``kernel-grid-cover``
    grid × block (via the evaluated ``index_map``) covers a different
    extent than the declared ``out_shape`` — the grid either misses part
    of the output or writes out of bounds.
``kernel-block-tile``
    Mosaic tiling: a block's lane dim must be a multiple of 128 and its
    sublane dim a multiple of 8, unless it spans the full (implied)
    array dim.
``kernel-dtype``
    A 64-bit ``out_shape`` dtype — does not propagate on TPU without
    x64 mode; the kernel would silently compute in 32 bits or fail.
``kernel-vmem-budget``
    Per-program resident block bytes (Σ in/out blocks) exceed the VMEM
    budget (default ~12 MiB of the ~16 MiB/core, CLI-configurable), or
    a contract's named budget invariant fails (e.g. the chunked dense
    carry plus a macro event slab at the caps, ``_ir_chunk_budget``).
``kernel-unresolved``
    The analyzer could not evaluate a shape it needed — a loud finding,
    never a silent pass, so adding symbols to a kernel without extending
    its contract fails the gate instead of going unchecked.

Scan set (CLI): ``ops/kernel_ir.py``, ``ops/dense_scan.py``,
``ops/linear_scan.py``, ``parallel/mesh.py``, ``history/packing.py`` —
the kernel IR carries THE chunk-carry bindings for every family that
chunks through it (``_ir_chunk_budget``; the per-family duplicates are
gone, PR 6), the other files are covered for their declared cap/budget
constants (incl. the macro-event ``MACRO_MAX_OPENS`` payload cap, whose
67-lane rows the chunk-slab bindings sample) and for any
``pallas_call`` a future PR adds there (none is in the tree since the
Pallas kernel left, PR 50).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from itertools import product
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from ..base import Finding, SourceFile, filter_allowed
from .interp import UNKNOWN, Closure, Dotted, Interp, _Abort, _Return

DEFAULT_VMEM_BUDGET = 12 << 20

#: dtypes that do not exist on TPU without jax x64 mode.
_BAD_DTYPES = {"float64", "int64", "uint64", "complex128"}

_DTYPE_BYTES = {"int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
                "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
                "int32": 4, "uint32": 4, "float32": 4}


@dataclass
class Contract:
    """Per-file symbol domains + named budget invariants."""

    #: symbol name -> candidate values (parameters of the functions
    #: enclosing the pallas_call); cross product, filtered by `where`.
    symbols: Dict[str, Tuple] = field(default_factory=dict)
    where: Optional[callable] = None
    #: (expr over module constants, max value, message) rows checked once.
    const_asserts: List[Tuple[str, int, str]] = field(default_factory=list)
    #: optional callable(interp) -> list of messages for file-specific
    #: budget invariants that need to *run* module functions. Each item
    #: is a bare message (emitted as kernel-vmem-budget) or an explicit
    #: (rule, message) pair — unresolved accounting must use
    #: ("kernel-unresolved", ...) so it stays loud under a
    #: kernel-vmem-budget baseline.
    custom: Optional[callable] = None


def _ir_chunk_budget(interp: Interp) -> List[str]:
    """THE chunk-carry contract bindings — proven once against the
    kernel IR (ops/kernel_ir.py) for every family that chunks through
    it, replacing the per-family dense/sort duplicates (PR 6
    satellite). The chunked entry points carry per-row scan state
    between kernel launches instead of rebuilding it — so the carry
    itself must fit the VMEM envelope at the eligibility caps, which
    live in the same module (a cap bump and an accounting change fail
    the gate together). Same loud-not-silent stance as the Pallas tile
    invariant: anything unresolvable is a kernel-unresolved finding."""
    out = []
    fn_d = interp.functions.get("dense_chunk_carry_bytes")
    caps_w = interp.module_env.get("DENSE_MAX_SLOTS")
    caps_s = interp.module_env.get("DENSE_MAX_STATES")
    mask_w = interp.module_env.get("MASK_DENSE_MAX_SLOTS")
    if fn_d is None or not all(isinstance(v, int)
                               for v in (caps_w, caps_s, mask_w)):
        out.append(("kernel-unresolved",
                    "dense_chunk_carry_bytes / dense caps not resolvable"))
    else:
        for W, S in ((1, 1), (caps_w, 1), (caps_w, caps_s), (mask_w, 1)):
            n = interp.exec_fn(fn_d, {"n_slots": W, "n_states": S})
            if not isinstance(n, int):
                out.append(("kernel-unresolved",
                            f"dense_chunk_carry_bytes({W}, {S}) "
                            "not evaluable"))
            elif n > 16 << 20:
                out.append(f"chunked dense carry (the domain family's "
                           f"frontier of one uint32 word a configuration, "
                           f"the mask family's bool column and subset "
                           f"sums) at (W={W}, S={S}) = {n} B exceeds "
                           "usable per-core VMEM")
    fn_s = interp.functions.get("sort_chunk_carry_bytes")
    n_cfg = interp.module_env.get("SORT_DEFAULT_CONFIGS")
    n_slots = interp.module_env.get("SORT_MAX_SLOTS")
    if fn_s is None or not all(isinstance(v, int)
                               for v in (n_cfg, n_slots)):
        out.append(("kernel-unresolved",
                    "sort_chunk_carry_bytes / sort caps not resolvable"))
    else:
        for C, W in ((n_cfg, 1), (n_cfg, n_slots), (4 * n_cfg, n_slots)):
            n = interp.exec_fn(fn_s, {"n_configs": C, "n_slots": W})
            if not isinstance(n, int):
                out.append(("kernel-unresolved",
                            f"sort_chunk_carry_bytes({C}, {W}) "
                            "not evaluable"))
            elif n > 16 << 20:
                out.append(f"chunked sort carry at (C={C}, W={W}) = {n} B "
                           "exceeds usable per-core VMEM")
    # Macro-event rows (ISSUE-4): the widened chunk event slab must
    # still fit next to the carry at the caps. MACRO_MAX_OPENS comes
    # from history/packing.py via the sibling-constant merge; a cap
    # bump that outgrows the proven bindings surfaces here, loudly.
    # Cycle-closure adjacency slab (ISSUE 13): the batched transitive-
    # closure kernel keeps the int32 adjacency matrix and its squared
    # product resident per row — proven at the CYCLE_MAX_NODES cap so
    # a cap bump fails the gate until the accounting is re-proven.
    fn_cy = interp.functions.get("cycle_adjacency_bytes")
    cap_n = interp.module_env.get("CYCLE_MAX_NODES")
    if fn_cy is None or not isinstance(cap_n, int):
        out.append(("kernel-unresolved",
                    "cycle_adjacency_bytes / CYCLE_MAX_NODES "
                    "not resolvable"))
    else:
        for N in (2, cap_n):
            n = interp.exec_fn(fn_cy, {"n_nodes": N})
            if not isinstance(n, int):
                out.append(("kernel-unresolved",
                            f"cycle_adjacency_bytes({N}) not evaluable"))
            elif n > 16 << 20:
                out.append(f"cycle adjacency slab at N={N} = {n} B "
                           "exceeds usable per-core VMEM")
    # Blocked-closure tile slab (ISSUE 19): the tiled kernel keeps a
    # [T,N] row panel, a [T,N] col panel, one streamed [T,N] product
    # panel and the [T,T] pivot diagonal resident — the budget binding
    # moves to TILE granularity, so the proof samples the tiled cap at
    # the default tile, the minimum tile, and the first post-monolithic
    # bucket. A cap or tile bump fails here until re-proven.
    fn_ct = interp.functions.get("cycle_closure_tile_bytes")
    cap_tn = interp.module_env.get("CYCLE_MAX_NODES_TILED")
    tile_t = interp.module_env.get("CYCLE_TILE")
    if fn_ct is None or not all(isinstance(v, int)
                                for v in (cap_tn, tile_t)):
        out.append(("kernel-unresolved",
                    "cycle_closure_tile_bytes / CYCLE_MAX_NODES_TILED / "
                    "CYCLE_TILE not resolvable"))
    else:
        for N, T in ((cap_tn, tile_t), (cap_tn, 2), (1024, tile_t)):
            n = interp.exec_fn(fn_ct, {"n_nodes": N, "tile": T})
            if not isinstance(n, int):
                out.append(("kernel-unresolved",
                            f"cycle_closure_tile_bytes({N}, {T}) "
                            "not evaluable"))
            elif n > 16 << 20:
                out.append(f"blocked cycle-closure tile slab at (N={N}, "
                           f"T={T}) = {n} B exceeds usable per-core VMEM")
    fn_r = interp.functions.get("macro_row_ints")
    cap_p = interp.module_env.get("MACRO_MAX_OPENS")
    if fn_r is None or not isinstance(cap_p, int):
        out.append(("kernel-unresolved",
                    "macro_row_ints / MACRO_MAX_OPENS not resolvable"))
        return out
    r = interp.exec_fn(fn_r, {"macro_p": cap_p})
    if not isinstance(r, int):
        out.append(("kernel-unresolved",
                    f"macro_row_ints({cap_p}) not evaluable"))
        return out
    # Carry + slab only when the dense half resolved — its absence was
    # already reported above with the RIGHT cause; re-blaming
    # macro_row_ints here would point the maintainer at the wrong fn.
    if fn_d is not None and all(isinstance(v, int)
                                for v in (caps_w, caps_s)):
        carry = interp.exec_fn(fn_d, {"n_slots": caps_w,
                                      "n_states": caps_s})
        if isinstance(carry, int) and carry + 4096 * r * 4 > 16 << 20:
            out.append(f"chunked dense carry + macro event slab at the "
                       f"caps = {carry + 4096 * r * 4} B exceeds usable "
                       "per-core VMEM")
    return out


CONTRACTS: Dict[str, Contract] = {
    "history/packing.py": Contract(const_asserts=[
        # The macro payload cap is load-bearing for every kernel
        # family's proven bindings: the chunk-slab checks sample rows
        # at 3 + 4·16 = 67 lanes, so a cap bump must fail here until
        # those bindings are re-proven.
        ("MACRO_MAX_OPENS", 16,
         "macro open cap outgrew the proven kernel-contract bindings "
         "(R = 67-lane rows); re-prove the chunk-slab budgets before "
         "raising it"),
        ("3 + 4 * MACRO_MAX_OPENS", 67,
         "macro row width beyond the proven R samples"),
    ]),
    # The IR owns the family caps and the chunk-carry accounting; its
    # contract carries THE single set of chunk-carry bindings
    # (_ir_chunk_budget) plus the cap const-asserts that used to live
    # per family.
    "ops/kernel_ir.py": Contract(const_asserts=[
        ("(1 << DENSE_MAX_SLOTS) * DENSE_MAX_STATES * 4", 16 << 20,
         "dense frontier at the eligibility caps exceeds VMEM"),
        ("DENSE_MAX_CELLS * 4", 16 << 20,
         "dense cell cap exceeds VMEM"),
        ("(1 << MASK_DENSE_MAX_SLOTS) * 8", 16 << 20,
         "mask frontier + subset-sum lane at the cap exceeds VMEM"),
        # 4 mask words must keep a spare top bit for the all-ones
        # empty-entry sentinel (linear_scan docstring soundness
        # argument).
        ("SORT_MAX_SLOTS", 127,
         "window cap would consume the sentinel bit of the last word"),
        ("SORT_DEFAULT_CONFIGS * ((SORT_MAX_SLOTS // 32 + 1) * 4 + 4)",
         16 << 20,
         "sort frontier at the default capacity exceeds VMEM"),
        # ISSUE 13: the cycle-closure adjacency + product slab at the
        # node cap (the custom binding also executes the accounting fn).
        ("2 * CYCLE_MAX_NODES * CYCLE_MAX_NODES * 4", 16 << 20,
         "cycle adjacency slab at the node cap exceeds VMEM"),
        # ISSUE 19: the blocked-closure tile slab at the TILED cap —
        # the per-tile binding (3 [T,N] panels + the [T,T] diagonal)
        # that lets N grow past the monolithic 512 cap. The custom
        # binding also executes cycle_closure_tile_bytes at corners.
        ("(3 * CYCLE_TILE * CYCLE_MAX_NODES_TILED + "
         "CYCLE_TILE * CYCLE_TILE) * 4", 16 << 20,
         "blocked cycle-closure tile slab at the tiled cap exceeds "
         "VMEM; re-prove before raising CYCLE_MAX_NODES_TILED or "
         "CYCLE_TILE"),
    ], custom=_ir_chunk_budget),
    "ops/dense_scan.py": Contract(const_asserts=[
        # Re-assert the caps through dense_scan's own import site: the
        # sibling-constant merge resolves them from kernel_ir, so a
        # broken re-export chain is a loud unresolved finding here.
        ("(1 << DENSE_MAX_SLOTS) * DENSE_MAX_STATES * 4", 16 << 20,
         "dense frontier at the eligibility caps exceeds VMEM"),
    ]),
    "ops/linear_scan.py": Contract(const_asserts=[
        ("MAX_SLOTS", 127,
         "window cap would consume the sentinel bit of the last word"),
    ]),
    "parallel/mesh.py": Contract(),
}

SCAN_FILES = tuple(CONTRACTS)


def applies_to(relpath: str) -> bool:
    rp = relpath.replace("\\", "/")
    rp = rp.split("jepsen_jgroups_raft_tpu/", 1)[-1]
    return rp in SCAN_FILES


def _contract_for(path: str) -> Contract:
    rp = str(path).replace("\\", "/")
    for key, c in CONTRACTS.items():
        if rp.endswith(key):
            return c
    return Contract()


# ------------------------------------------------------------ extraction


def _leaf(call: ast.Call) -> str:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return ""


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _enclosing_chain(tree: ast.Module) -> List[Tuple[ast.Call, list]]:
    """[(pallas_call node, [enclosing FunctionDefs outer→inner])]."""
    out = []

    def walk(node, chain):
        for child in ast.iter_child_nodes(node):
            nc = chain
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nc = chain + [child]
            if isinstance(child, ast.Call) and \
                    _leaf(child) == "pallas_call":
                out.append((child, list(nc)))
            walk(child, nc)

    walk(tree, [])
    return out


def _merge_sibling_consts(interp: Interp, tree: ast.Module,
                          path: str) -> None:
    """Resolve relative-import constants (`from .sibling import NAME`,
    `from ..pkg.mod import NAME`) so cross-module cap expressions stay
    checkable — dense_scan re-asserts kernel_ir's caps, and kernel_ir's
    macro-row bindings use history/packing.py's MACRO_MAX_OPENS."""
    base = Path(path).parent
    for stmt in tree.body:
        if not (isinstance(stmt, ast.ImportFrom) and stmt.level >= 1
                and stmt.module):
            continue
        target = base
        for _ in range(stmt.level - 1):
            target = target.parent
        sib = target.joinpath(*stmt.module.split(".")).with_suffix(".py")
        if not sib.exists():
            continue
        try:
            sub = Interp(ast.parse(sib.read_text(encoding="utf-8",
                                                 errors="replace")))
        except SyntaxError:
            continue
        for alias in stmt.names:
            val = sub.module_env.get(alias.name, UNKNOWN)
            if val is not UNKNOWN:
                interp.module_env.setdefault(alias.asname or alias.name,
                                             val)


# -------------------------------------------------------------- checking


def _bindings(contract: Contract):
    if not contract.symbols:
        return [{}]
    names = sorted(contract.symbols)
    out = []
    for combo in product(*(contract.symbols[n] for n in names)):
        b = dict(zip(names, combo))
        if contract.where is None or contract.where(b):
            out.append(b)
    return out


def _eval_specs(interp: Interp, expr: Optional[ast.expr], env: dict):
    """BlockSpec list/single ast -> [(shape tuple, index_map Closure)]
    or None when unresolvable."""
    if expr is None:
        return []
    elts = expr.elts if isinstance(expr, (ast.List, ast.Tuple)) else [expr]
    specs = []
    for e in elts:
        if not (isinstance(e, ast.Call) and _leaf(e) == "BlockSpec"):
            return None
        shape_ast = e.args[0] if e.args else _kw(e, "block_shape")
        imap_ast = e.args[1] if len(e.args) > 1 else _kw(e, "index_map")
        shape = interp.eval(shape_ast, env) if shape_ast is not None \
            else None
        if not (isinstance(shape, tuple) and
                all(isinstance(d, int) and d > 0 for d in shape)):
            return None
        imap = interp.eval(imap_ast, env) if imap_ast is not None else None
        specs.append((shape, imap if isinstance(imap, Closure) else None))
    return specs


def _eval_out_shapes(interp: Interp, expr: Optional[ast.expr], env: dict):
    """out_shape ast -> [(shape tuple, dtype leaf str)] or None."""
    if expr is None:
        return None
    elts = expr.elts if isinstance(expr, (ast.List, ast.Tuple)) else [expr]
    out = []
    for e in elts:
        if not (isinstance(e, ast.Call) and
                _leaf(e) == "ShapeDtypeStruct" and len(e.args) >= 2):
            return None
        shape = interp.eval(e.args[0], env)
        dtype = interp.eval(e.args[1], env)
        if not (isinstance(shape, tuple) and
                all(isinstance(d, int) and d > 0 for d in shape)):
            return None
        out.append((shape, dtype.leaf if isinstance(dtype, Dotted)
                    else str(dtype)))
    return out


def _grid_points(grid: Tuple[int, ...]):
    total = 1
    for g in grid:
        total *= g
    if total <= 4096:
        return product(*(range(g) for g in grid))
    # corner sampling for huge grids: extremes bound the index maps the
    # repo writes (affine in program ids)
    return product(*(sorted({0, g - 1}) for g in grid))


def _implied_extent(shape, imap, grid):
    """(max block origin + 1) * block per dim, from evaluating the
    index map over the grid; None when the map is unresolvable."""
    if imap is None:
        return None
    maxo = [0] * len(shape)
    for point in _grid_points(grid):
        origins = imap.call(list(point))
        if not (isinstance(origins, tuple) and len(origins) == len(shape)
                and all(isinstance(o, int) and o >= 0 for o in origins)):
            return None
        for d, o in enumerate(origins):
            maxo[d] = max(maxo[d], o)
    return tuple((m + 1) * s for m, s in zip(maxo, shape))


def _tile_violations(shape, implied) -> List[str]:
    if len(shape) < 2:
        return []
    if implied is None:
        # no (resolvable) index_map: pallas defaults to a whole-array
        # block, which spans the full dims by definition — there is no
        # tile violation to assert, and claiming one would flag every
        # default BlockSpec.
        return []
    out = []
    lane, sub = shape[-1], shape[-2]
    full_lane = implied[-1]
    full_sub = implied[-2]
    if lane % 128 and lane != full_lane:
        out.append(f"lane dim {lane} is neither a multiple of 128 nor "
                   f"the full array dim ({full_lane})")
    if sub % 8 and sub != full_sub:
        out.append(f"sublane dim {sub} is neither a multiple of 8 nor "
                   f"the full array dim ({full_sub})")
    return out


def _check_call(call: ast.Call, chain: list, contract: Contract,
                interp: Interp, budget: int) -> List[Tuple[str, str]]:
    """One pallas_call over every contract binding -> [(rule, message)],
    deduped (first offending binding reported)."""
    seen = {}
    for binding in _bindings(contract):
        env = dict(binding)
        aborted = False
        for fn in chain:
            args = fn.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                env.setdefault(a.arg, UNKNOWN)
            try:
                interp.lenient = True
                interp.exec_body(fn.body, env)
            except _Return:
                pass
            except _Abort:
                # e.g. a loop past the interpreter's iteration ceiling:
                # the harvested env is partial and untrustworthy, so the
                # sample is reported unresolved below — a loud finding,
                # never a crashed lint run or a shape check against
                # half-evaluated values.
                aborted = True
            finally:
                interp.lenient = False

        def unresolved(what):
            seen.setdefault(("kernel-unresolved", what),
                            f"cannot statically evaluate {what} — extend "
                            "the file's contract in lint/flow/"
                            "kernel_contract.py or simplify the "
                            "expression")

        if aborted:
            unresolved("the enclosing scope (interpreter abort)")
            continue

        grid_ast = _kw(call, "grid")
        grid = interp.eval(grid_ast, env) if grid_ast is not None else ()
        if isinstance(grid, int):
            grid = (grid,)
        if not (isinstance(grid, tuple) and
                all(isinstance(g, int) and g > 0 for g in grid)):
            unresolved("grid")
            continue
        in_specs = _eval_specs(interp, _kw(call, "in_specs"), env)
        out_specs = _eval_specs(interp, _kw(call, "out_specs"), env)
        out_shapes = _eval_out_shapes(interp, _kw(call, "out_shape"), env)
        if in_specs is None:
            unresolved("in_specs")
            continue
        if out_specs is None or out_shapes is None:
            unresolved("out_specs/out_shape")
            continue

        blocks_bytes = 0
        for shape, imap in in_specs:
            implied = _implied_extent(shape, imap, grid)
            for v in _tile_violations(shape, implied):
                seen.setdefault(("kernel-block-tile", v),
                                f"in_spec block {shape} at {binding}: {v}")
            blocks_bytes += _prod(shape) * 4  # int32-dominated inputs

        for i, (shape, imap) in enumerate(out_specs):
            decl, dtype = out_shapes[i] if i < len(out_shapes) else \
                (None, "int32")
            if dtype in _BAD_DTYPES:
                seen.setdefault(("kernel-dtype", dtype),
                                f"out_shape dtype {dtype}: 64-bit dtypes "
                                "do not propagate on TPU (x64 off)")
            nbytes = _DTYPE_BYTES.get(dtype, 4)
            blocks_bytes += _prod(shape) * nbytes
            if decl is not None:
                if len(decl) != len(shape):
                    seen.setdefault(
                        ("kernel-block-divide", f"rank{i}"),
                        f"out_spec block {shape} rank differs from "
                        f"out_shape {decl}")
                    continue
                for d, (b, a) in enumerate(zip(shape, decl)):
                    if a % b:
                        seen.setdefault(
                            ("kernel-block-divide", f"{i}.{d}"),
                            f"out_spec block dim {b} does not divide "
                            f"out_shape dim {a} (axis {d}, at {binding})")
                implied = _implied_extent(shape, imap, grid)
                if implied is not None and implied != decl:
                    seen.setdefault(
                        ("kernel-grid-cover", str(i)),
                        f"grid {grid} × block {shape} covers {implied} "
                        f"but out_shape declares {decl} (at {binding})")
                for v in _tile_violations(shape, decl):
                    seen.setdefault(("kernel-block-tile", f"out:{v}"),
                                    f"out_spec block {shape}: {v}")

        if blocks_bytes > budget:
            seen.setdefault(
                ("kernel-vmem-budget", "blocks"),
                f"resident blocks ≈ {blocks_bytes} B exceed the VMEM "
                f"budget {budget} B (at {binding}; --vmem-budget to "
                "raise)")
    return [(rule, msg) for (rule, _detail), msg in seen.items()]


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= d
    return out


# ------------------------------------------------------------- interface


def analyze_source(src: SourceFile,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> List[Finding]:
    try:
        tree = ast.parse(src.text)
    except SyntaxError as e:
        return [Finding(src.path, e.lineno or 1, "parse-error", str(e))]
    contract = _contract_for(src.path)
    interp = Interp(tree)
    _merge_sibling_consts(interp, tree, src.path)
    findings: List[Finding] = []

    for expr, limit, msg in contract.const_asserts:
        try:
            val = interp.eval(ast.parse(expr, mode="eval").body, {})
        except SyntaxError:
            val = UNKNOWN
        if not isinstance(val, int):
            findings.append(Finding(
                src.path, 1, "kernel-unresolved",
                f"budget expression {expr!r} not evaluable from module "
                "constants"))
        elif val > limit:
            findings.append(Finding(
                src.path, 1, "kernel-vmem-budget",
                f"{expr} = {val} > {limit}: {msg}"))

    if contract.custom is not None:
        # Custom analyzers yield either a bare message (a budget
        # violation) or an explicit (rule, message) pair — unresolved
        # accounting must surface under kernel-unresolved, the loud
        # could-not-evaluate rule, so baselining kernel-vmem-budget
        # can never swallow a vanished accounting fn.
        for item in contract.custom(interp):
            rule, msg = (item if isinstance(item, tuple)
                         else ("kernel-vmem-budget", item))
            findings.append(Finding(src.path, 1, rule, msg))

    for call, chain in _enclosing_chain(tree):
        for rule, msg in _check_call(call, chain, contract, interp,
                                     vmem_budget):
            findings.append(Finding(src.path, call.lineno, rule, msg))
    return filter_allowed(src, findings)


def analyze_file(path, vmem_budget: int = DEFAULT_VMEM_BUDGET
                 ) -> List[Finding]:
    return analyze_source(SourceFile.load(path), vmem_budget)
