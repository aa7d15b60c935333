"""R004 — Pallas kernel hygiene.

Four checks on every ``pl.pallas_call`` site in ``kernels/``:

* **divisibility guard**: the wrapper function must assert (or
  if-raise) a ``%``-divisibility relation before launching — a grid of
  ``n // tile`` with ``n % tile != 0`` silently drops the tail rows on
  TPU rather than erroring (guide: grid x BlockSpec must tile the padded
  array exactly).
* **host ops in the kernel body**: ``np.*`` / ``print`` / ``.item()``
  inside the kernel function run at trace time on the host — at best a
  constant bake-in, at worst a TracerError on Mosaic lowering.
* **VMEM footprint**: when every BlockSpec block shape resolves to int
  literals (directly or via module constants), the per-step resident
  estimate (4 bytes/elem across in+out blocks) must stay under a
  configurable ceiling (default 16 MB of the ~64 MB/core budget —
  headroom for double-buffering and scratch).  Symbolic shapes (the
  production kernels size blocks from runtime args) are skipped.
* **equality-cube budget**: a kernel that materialises the (B, D, D)
  equality cube (``lab[:, :, None] == lab[:, None, :]``, directly or via
  the shared ``argmax_tile_math`` tile math) allocates VMEM the
  BlockSpecs never see — its wrapper must assert the cube product
  against a budget (``tile_b * d * d * 4 <= CUBE_LIMIT_BYTES``) before
  launching, or an oversized tile choice OOMs only at Mosaic compile
  time on hardware.
"""
from __future__ import annotations

import ast

from repro.analysis.findings import Finding
from repro.analysis.rules.base import (
    ModuleContext,
    Rule,
    _const_int,
    dotted_name,
    function_map,
    module_int_constants,
)

_DEFAULT_VMEM_CEILING = 16 * 2 ** 20   # bytes per grid step, in+out blocks

_HOST_ROOTS = {"np", "numpy"}
_HOST_METHODS = {"item", "tolist"}


def _is_pallas_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return name is not None and name.split(".")[-1] == "pallas_call"


def _resolve_kernel(call: ast.Call,
                    by_name: dict[str, ast.FunctionDef]
                    ) -> ast.FunctionDef | None:
    """The kernel function passed as pallas_call's first argument
    (through a ``partial(kernel, ...)`` wrapper if present)."""
    if not call.args:
        return None
    target = call.args[0]
    if isinstance(target, ast.Call) \
            and dotted_name(target.func) in ("partial", "functools.partial") \
            and target.args:
        target = target.args[0]
    name = dotted_name(target)
    return by_name.get(name) if name else None


# Shared tile-math helpers known to build the (B, D, D) equality cube;
# fused_sweep.py imports argmax_tile_math so the cube never appears
# literally in its kernel bodies.
_CUBE_HELPERS = {"argmax_tile_math"}


def _is_rank3_broadcast(node: ast.expr) -> bool:
    """``x[:, :, None]``-style subscript: >=3-elt slice tuple with None."""
    if not isinstance(node, ast.Subscript) \
            or not isinstance(node.slice, ast.Tuple) \
            or len(node.slice.elts) < 3:
        return False
    return any(isinstance(e, ast.Constant) and e.value is None
               for e in node.slice.elts)


def _materialises_cube(fn: ast.FunctionDef,
                       by_name: dict[str, ast.FunctionDef],
                       _seen: set[str] | None = None) -> bool:
    """Equality-cube pattern in ``fn``, directly (a compare of two rank-3
    broadcast subscripts) or through module-local / shared helpers."""
    _seen = set() if _seen is None else _seen
    if fn.name in _seen:
        return False
    _seen.add(fn.name)
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare):
            sides = [node.left, *node.comparators]
            if sum(_is_rank3_broadcast(s) for s in sides) >= 2:
                return True
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            leaf = name.split(".")[-1] if name else None
            if leaf in _CUBE_HELPERS:
                return True
            local = by_name.get(leaf) if leaf else None
            if local is not None \
                    and _materialises_cube(local, by_name, _seen):
                return True
    return False


def _has_cube_budget_assert(fn: ast.FunctionDef) -> bool:
    """An assert bounding a product: contains both a ``*`` and a
    ``<``/``<=`` (the ``tile_b * d * d * 4 <= BUDGET`` shape)."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assert):
            continue
        sub = list(ast.walk(node.test))
        has_mult = any(isinstance(s, ast.BinOp)
                       and isinstance(s.op, ast.Mult) for s in sub)
        has_bound = any(isinstance(s, ast.Compare)
                        and any(isinstance(op, (ast.Lt, ast.LtE))
                                for op in s.ops) for s in sub)
        if has_mult and has_bound:
            return True
    return False


def _has_divisibility_guard(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        test = None
        if isinstance(node, ast.Assert):
            test = node.test
        elif isinstance(node, ast.If) \
                and any(isinstance(b, ast.Raise) for b in node.body):
            test = node.test
        if test is not None and any(
                isinstance(s, ast.BinOp) and isinstance(s.op, ast.Mod)
                for s in ast.walk(test)):
            return True
    return False


def _block_nbytes(call: ast.Call, env: dict[str, int]) -> int | None:
    """Summed in+out block bytes when every BlockSpec shape is concrete;
    None as soon as one dimension stays symbolic."""
    total = 0
    seen = False
    for node in ast.walk(call):
        if not (isinstance(node, ast.Call)
                and dotted_name(node.func) is not None
                and dotted_name(node.func).split(".")[-1] == "BlockSpec"
                and node.args):
            continue
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)):
            return None
        elems = 1
        for dim in shape.elts:
            v = _const_int(dim, env)
            if v is None:
                return None
            elems *= v
        total += elems * 4
        seen = True
    return total if seen else None


class PallasRule(Rule):
    id = "R004"
    tag = "pallas"
    description = ("pallas_call hygiene: grid divisibility guard, no host "
                   "ops in kernel bodies, VMEM block footprint ceiling, "
                   "equality-cube budget assert")

    def __init__(self, vmem_ceiling: int = _DEFAULT_VMEM_CEILING):
        self.vmem_ceiling = int(vmem_ceiling)

    def applies(self, relpath: str) -> bool:
        return relpath.startswith("kernels/")

    def check(self, ctx: ModuleContext) -> list[Finding]:
        findings: list[Finding] = []
        owner = function_map(ctx.tree)
        consts = module_int_constants(ctx.tree)
        by_name = {n.name: n for n in ast.walk(ctx.tree)
                   if isinstance(n, ast.FunctionDef)}
        checked_kernels: set[int] = set()

        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and _is_pallas_call(node)):
                continue

            wrapper = owner.get(id(node))
            if wrapper is None or not _has_divisibility_guard(wrapper):
                where = f"'{wrapper.name}'" if wrapper else "module scope"
                findings.append(self.finding(
                    ctx, node,
                    f"pallas_call in {where} without a grid-divisibility "
                    f"guard (assert/raise on `% tile == 0`) — a non-tiling "
                    f"grid silently drops tail rows on TPU"))

            kernel = _resolve_kernel(node, by_name)
            if kernel is not None and id(kernel) not in checked_kernels:
                checked_kernels.add(id(kernel))
                findings.extend(self._check_kernel_body(ctx, kernel))

            if kernel is not None \
                    and _materialises_cube(kernel, by_name) \
                    and (wrapper is None
                         or not _has_cube_budget_assert(wrapper)):
                findings.append(self.finding(
                    ctx, node,
                    f"kernel '{kernel.name}' materialises the (B, D, D) "
                    f"equality cube — VMEM the BlockSpecs never see — but "
                    f"its wrapper has no cube-budget assert "
                    f"(`tile_b * d * d * 4 <= CUBE_LIMIT_BYTES`)"))

            nbytes = _block_nbytes(node, consts)
            if nbytes is not None and nbytes > self.vmem_ceiling:
                findings.append(self.finding(
                    ctx, node,
                    f"pallas_call block footprint ~{nbytes // 1024} KiB "
                    f"exceeds the VMEM ceiling "
                    f"({self.vmem_ceiling // 1024} KiB) — shrink the block "
                    f"shapes or raise --vmem-ceiling with a justification"))
        return findings

    def _check_kernel_body(self, ctx: ModuleContext,
                           kernel: ast.FunctionDef) -> list[Finding]:
        out = []
        for node in ast.walk(kernel):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            bad = None
            if name and name.split(".")[0] in _HOST_ROOTS:
                bad = f"{name}()"
            elif isinstance(node.func, ast.Name) and node.func.id == "print":
                bad = "print()"
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _HOST_METHODS:
                bad = f".{node.func.attr}()"
            if bad:
                out.append(self.finding(
                    ctx, node,
                    f"host op {bad} inside pallas kernel '{kernel.name}' — "
                    f"kernel bodies lower through Mosaic; host calls run at "
                    f"trace time (constant bake-in) or fail to lower"))
        return out
