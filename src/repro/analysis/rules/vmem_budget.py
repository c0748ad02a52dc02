"""Rule ``vmem-budget`` — static VMEM residency accounting for kernels.

A TPU core has ~16 MiB of VMEM.  Every Pallas kernel in
``src/repro/kernels/`` declares its working set statically: BlockSpec
block shapes (inputs/outputs) plus ``pltpu.VMEM`` scratch.  This pass
evaluates those shapes symbolically against the production config
(§5.1: d=256, 100 negatives, 5000/50 RQ codebooks, queue_len=256,
64k x 32 I2I table, 64x5 PPR walks) and fails any ``pallas_call`` whose
estimated residency exceeds the budget.

Accounting model (matches the double-buffering the Mosaic pipeline
actually does):

* a block whose ``index_map`` *references* a grid parameter changes per
  program -> it streams, double-buffered, **x2**;
* a block whose ``index_map`` is constant (``lambda b: (0, 0)``) — or
  absent — is fetched once and stays **resident, x1**;
* scratch is resident, sized by its declared dtype;
* elements default to 4 bytes (every kernel in-tree moves f32/int32
  blocks).

Dimension names resolve, in order: function-local constant assignments
(``S = n_walks * walk_len``) -> the per-kernel production table below ->
module-wide keyword defaults scraped from signatures (``block_b: int =
32``) -> the global table.  A spec that still doesn't resolve is counted
in the report as unresolved and never fails the budget.

``finalize`` writes the full residency table to
``benchmarks/results/vmem_report.json`` (see ``benchmarks/run.py``).
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.base import Finding, ModuleContext, Rule, dotted_name

DEFAULT_BUDGET_BYTES = 16 * 1024 * 1024

#: production dims (configs/rankgraph2.py §5.1), keyed by kernel package
MODULE_DIMS: Dict[str, Dict[str, int]] = {
    "queue_gather": {"Q": 256, "N": 65536, "K": 32, "n_recent": 8,
                     "k": 64, "B": 1024},
    "ppr_walk": {"N": 131072, "D2": 64, "n_walks": 64, "walk_len": 5},
    "rq_assign": {"d": 256, "L": 2},
    "embedding_bag": {"D": 256, "L": 32, "B": 32768},
    "fused_contrastive": {"d": 256, "N": 100},
    "flash_attention": {"D": 128},
}

GLOBAL_DIMS: Dict[str, int] = {"d": 256, "D": 256, "L": 2}

#: expression sequences a ListComp expands over — `in_specs += [
#: pl.BlockSpec(c.shape, ...) for c in codebooks]` binds `c.shape` to
#: the production codebook shapes
MODULE_EXPR_SEQS: Dict[str, Dict[str, List[Tuple[int, ...]]]] = {
    "rq_assign": {"c.shape": [(5000, 256), (50, 256)]},
}

DTYPE_BYTES = {"float64": 8, "int64": 8, "uint64": 8,
               "float32": 4, "int32": 4, "uint32": 4,
               "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
               "int8": 1, "uint8": 1, "bool_": 1, "bool": 1}


@dataclasses.dataclass
class SpecInfo:
    kind: str                 # "in" | "out" | "scratch"
    shape: Optional[Tuple[int, ...]]
    bytes: int
    streaming: bool
    resolved: bool

    def to_dict(self) -> Dict[str, object]:
        return {"kind": self.kind,
                "shape": list(self.shape) if self.shape else None,
                "bytes": self.bytes, "streaming": self.streaming,
                "resolved": self.resolved}


class _Unresolved(Exception):
    pass


class _Evaluator:
    """Integer-evaluate shape expressions against the dims env."""

    def __init__(self, local: Dict[str, int], *envs: Dict[str, int]):
        self.local = local
        self.envs = envs

    def lookup(self, name: str) -> int:
        if name in self.local:
            return self.local[name]
        for env in self.envs:
            if name in env:
                return env[name]
        raise _Unresolved(name)

    def eval(self, node: ast.AST) -> int:
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        if isinstance(node, ast.Name):
            return self.lookup(node.id)
        if isinstance(node, ast.BinOp):
            a, b = self.eval(node.left), self.eval(node.right)
            if isinstance(node.op, ast.Add):
                return a + b
            if isinstance(node.op, ast.Sub):
                return a - b
            if isinstance(node.op, ast.Mult):
                return a * b
            if isinstance(node.op, (ast.FloorDiv, ast.Div)):
                return a // b
            if isinstance(node.op, ast.Mod):
                return a % b
            raise _Unresolved(ast.dump(node.op))
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname in ("min", "max") and node.args and not node.keywords:
                vals = [self.eval(a) for a in node.args]
                return min(vals) if fname == "min" else max(vals)
        raise _Unresolved(ast.unparse(node))

    def eval_shape(self, node: ast.AST) -> Tuple[int, ...]:
        if isinstance(node, (ast.Tuple, ast.List)):
            # a ``None`` block dim is squeezed: one element along it
            return tuple(1 if isinstance(e, ast.Constant) and e.value is None
                         else self.eval(e) for e in node.elts)
        raise _Unresolved(ast.unparse(node))


def _module_key(path: str) -> str:
    parts = path.replace("\\", "/").split("/")
    return parts[-2] if len(parts) >= 2 else ""


def _scrape_param_defaults(tree: ast.Module) -> Dict[str, int]:
    """``def f(..., block_b: int = 32)`` -> {"block_b": 32}; conflicting
    defaults keep the max (conservative for a budget check)."""
    out: Dict[str, int] = {}
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        for args, defaults in ((fn.args.args, fn.args.defaults),
                               (fn.args.kwonlyargs, fn.args.kw_defaults)):
            pos = args[len(args) - len(defaults):] \
                if defaults is not fn.args.kw_defaults else args
            for arg, dflt in zip(pos, defaults):
                if isinstance(dflt, ast.Constant) and isinstance(
                        dflt.value, int) and not isinstance(
                            dflt.value, bool):
                    out[arg.arg] = max(out.get(arg.arg, 0), dflt.value)
    return out


def _scrape_module_ints(tree: ast.Module) -> Dict[str, int]:
    """Module-level integer constants (``_ROWS = 8``)."""
    out: Dict[str, int] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)
                and not isinstance(node.value.value, bool)):
            out[node.targets[0].id] = node.value.value
    return out


#: memory spaces a BlockSpec can name that are not VMEM
_NON_VMEM_SPACES = ("SMEM", "ANY", "HBM")


def _index_map_streams(node: Optional[ast.AST]) -> bool:
    """True when the index_map output depends on a grid parameter."""
    if not isinstance(node, ast.Lambda):
        return node is not None       # non-lambda map: assume it varies
    params = {a.arg for a in node.args.args}
    return any(isinstance(n, ast.Name) and n.id in params
               for n in ast.walk(node.body))


class VmemBudgetRule(Rule):
    name = "vmem-budget"
    description = ("estimated VMEM residency of every pallas_call "
                   "(blocks x double-buffering + scratch) must fit the "
                   "per-core budget at production dims")

    def __init__(self, budget_bytes: int = DEFAULT_BUDGET_BYTES,
                 report_path: Optional[str] = None):
        self.budget_bytes = budget_bytes
        self.report_path = report_path
        self.entries: List[Dict[str, object]] = []

    def applies(self, path: str) -> bool:
        return "kernels" in path.replace("\\", "/").split("/")

    # -- entry point --------------------------------------------------------

    def check(self, ctx: ModuleContext) -> List[Finding]:
        findings: List[Finding] = []
        mod = _module_key(ctx.path)
        mod_dims = MODULE_DIMS.get(mod, {})
        sig_dims = {**_scrape_param_defaults(ctx.tree),
                    **_scrape_module_ints(ctx.tree)}
        expr_seqs = MODULE_EXPR_SEQS.get(mod, {})

        for fn in [n for n in ast.walk(ctx.tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]:
            assigns, lists = self._function_bindings(fn)
            local = self._const_locals(assigns, mod_dims, sig_dims)
            ev = _Evaluator(local, mod_dims, sig_dims, GLOBAL_DIMS)
            for call in [n for n in ast.walk(fn)
                         if isinstance(n, ast.Call)
                         and dotted_name(n.func).split(".")[-1]
                         == "pallas_call"]:
                self._check_call(ctx, fn, call, ev, assigns, lists,
                                 expr_seqs, findings)
        return findings

    # -- per-function binding collection ------------------------------------

    @staticmethod
    def _function_bindings(fn: ast.FunctionDef
                           ) -> Tuple[Dict[str, ast.expr],
                                      Dict[str, List[ast.expr]]]:
        assigns: Dict[str, ast.expr] = {}
        lists: Dict[str, List[ast.expr]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if not isinstance(t, ast.Name):
                        continue
                    assigns[t.id] = node.value
                    if isinstance(node.value, (ast.List, ast.Tuple)):
                        lists[t.id] = list(node.value.elts)
                    elif isinstance(node.value, ast.ListComp):
                        lists[t.id] = [node.value]
            elif isinstance(node, ast.AugAssign) and isinstance(
                    node.op, ast.Add) and isinstance(node.target, ast.Name):
                ext = lists.setdefault(node.target.id, [])
                if isinstance(node.value, (ast.List, ast.Tuple)):
                    ext.extend(node.value.elts)
                else:
                    ext.append(node.value)
        return assigns, lists

    @staticmethod
    def _const_locals(assigns: Dict[str, ast.expr],
                      mod_dims: Dict[str, int],
                      sig_dims: Dict[str, int]) -> Dict[str, int]:
        """Fixed-point evaluation of constant local assignments
        (``S = n_walks * walk_len``) against the dims tables."""
        local: Dict[str, int] = {}
        for _ in range(4):
            progress = False
            ev = _Evaluator(local, mod_dims, sig_dims, GLOBAL_DIMS)
            for name, expr in assigns.items():
                if name in local:
                    continue
                try:
                    local[name] = ev.eval(expr)
                    progress = True
                except _Unresolved:
                    pass
            if not progress:
                break
        return local

    # -- per-call accounting ------------------------------------------------

    def _check_call(self, ctx: ModuleContext, fn: ast.FunctionDef,
                    call: ast.Call, ev: _Evaluator,
                    assigns: Dict[str, ast.expr],
                    lists: Dict[str, List[ast.expr]],
                    expr_seqs: Dict[str, List[Tuple[int, ...]]],
                    findings: List[Finding]) -> None:
        kw = {k.arg: k.value for k in call.keywords}
        in_specs, out_specs = kw.get("in_specs"), kw.get("out_specs")
        scratch = kw.get("scratch_shapes")
        grid_spec = kw.get("grid_spec")
        if isinstance(grid_spec, ast.Name):
            grid_spec = assigns.get(grid_spec.id)
        if isinstance(grid_spec, ast.Call):
            gkw = {k.arg: k.value for k in grid_spec.keywords}
            in_specs = in_specs or gkw.get("in_specs")
            out_specs = out_specs or gkw.get("out_specs")
            scratch = scratch or gkw.get("scratch_shapes")

        specs: List[SpecInfo] = []
        for kind, group in (("in", in_specs), ("out", out_specs)):
            for expr in self._iter_spec_exprs(group, assigns, lists):
                specs.append(self._eval_spec(kind, expr, ev, expr_seqs))
        for expr in self._iter_list(scratch, lists):
            specs.append(self._eval_scratch(expr, ev))
        # an expr-seq spec expands to several concrete specs
        flat: List[SpecInfo] = []
        for s in specs:
            flat.extend(s if isinstance(s, list) else [s])

        total = sum(s.bytes for s in flat)
        unresolved = sum(1 for s in flat if not s.resolved)
        entry = {
            "kernel": f"{_module_key(ctx.path)}:{fn.name}",
            "path": ctx.path, "line": call.lineno,
            "vmem_bytes": total,
            "vmem_mib": round(total / (1024 * 1024), 3),
            "budget_bytes": self.budget_bytes,
            "over_budget": total > self.budget_bytes,
            "unresolved_specs": unresolved,
            "specs": [s.to_dict() for s in flat],
        }
        self.entries.append(entry)
        if total > self.budget_bytes:
            findings.append(Finding(
                self.name, ctx.path, call.lineno, call.col_offset,
                f"pallas_call in `{fn.name}` needs ~"
                f"{entry['vmem_mib']} MiB of VMEM at production dims "
                f"(budget {self.budget_bytes // (1024 * 1024)} MiB) — "
                f"shrink the block tiles or stream the resident "
                f"operand from HBM"))

    def _iter_list(self, group: Optional[ast.AST],
                   lists: Dict[str, List[ast.expr]]) -> List[ast.expr]:
        if group is None:
            return []
        if isinstance(group, ast.Name):
            return lists.get(group.id, [])
        if isinstance(group, (ast.List, ast.Tuple)):
            return list(group.elts)
        return [group]

    def _iter_spec_exprs(self, group: Optional[ast.AST],
                         assigns: Dict[str, ast.expr],
                         lists: Dict[str, List[ast.expr]]
                         ) -> List[ast.expr]:
        out: List[ast.expr] = []
        for expr in self._iter_list(group, lists):
            if isinstance(expr, ast.Name):      # row/col/neg spec aliases
                expr = assigns.get(expr.id, expr)
            out.append(expr)
        return out

    def _eval_spec(self, kind: str, expr: ast.expr, ev: _Evaluator,
                   expr_seqs: Dict[str, List[Tuple[int, ...]]]):
        if isinstance(expr, ast.ListComp):
            return self._expand_comp(kind, expr, ev, expr_seqs)
        if not isinstance(expr, ast.Call):
            return SpecInfo(kind, None, 0, False, False)
        shape_arg = expr.args[0] if expr.args else None
        imap = expr.args[1] if len(expr.args) > 1 else None
        for k in expr.keywords:
            if k.arg == "index_map":
                imap = k.value
            if k.arg == "memory_space" and dotted_name(k.value).split(
                    ".")[-1] in _NON_VMEM_SPACES:
                return SpecInfo(kind, None, 0, False, True)
        streams = _index_map_streams(imap)
        if isinstance(shape_arg, (ast.Tuple, ast.List)):
            try:
                shape = ev.eval_shape(shape_arg)
            except _Unresolved:
                return SpecInfo(kind, None, 0, streams, False)
            nbytes = _prod(shape) * 4 * (2 if streams else 1)
            return SpecInfo(kind, shape, nbytes, streams, True)
        if shape_arg is not None:
            key = ast.unparse(shape_arg)
            if key in expr_seqs:               # rare: direct expr binding
                return [SpecInfo(kind, s, _prod(s) * 4 *
                                 (2 if streams else 1), streams, True)
                        for s in expr_seqs[key]]
        return SpecInfo(kind, None, 0, streams, False)

    def _expand_comp(self, kind: str, comp: ast.ListComp, ev: _Evaluator,
                     expr_seqs: Dict[str, List[Tuple[int, ...]]]
                     ) -> List[SpecInfo]:
        """``[pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in cbs]`` —
        the loop expression's values come from MODULE_EXPR_SEQS."""
        elt = comp.elt
        if not isinstance(elt, ast.Call) or not elt.args:
            return [SpecInfo(kind, None, 0, False, False)]
        imap = elt.args[1] if len(elt.args) > 1 else None
        streams = _index_map_streams(imap)
        key = ast.unparse(elt.args[0])
        if key in expr_seqs:
            return [SpecInfo(kind, s, _prod(s) * 4 *
                             (2 if streams else 1), streams, True)
                    for s in expr_seqs[key]]
        try:
            shape = ev.eval_shape(elt.args[0])
        except _Unresolved:
            return [SpecInfo(kind, None, 0, streams, False)]
        return [SpecInfo(kind, shape, _prod(shape) * 4 *
                         (2 if streams else 1), streams, True)]

    def _eval_scratch(self, expr: ast.expr, ev: _Evaluator) -> SpecInfo:
        if not isinstance(expr, ast.Call) or not expr.args:
            return SpecInfo("scratch", None, 0, False, False)
        try:
            shape = ev.eval_shape(expr.args[0])
        except _Unresolved:
            return SpecInfo("scratch", None, 0, False, False)
        elem = 4
        if len(expr.args) > 1:
            dt = dotted_name(expr.args[1]).split(".")[-1]
            elem = DTYPE_BYTES.get(dt, 4)
        return SpecInfo("scratch", shape, _prod(shape) * elem, False, True)

    # -- report -------------------------------------------------------------

    def finalize(self) -> List[Finding]:
        if self.report_path and self.entries:
            os.makedirs(os.path.dirname(self.report_path) or ".",
                        exist_ok=True)
            report = {
                "budget_bytes": self.budget_bytes,
                "budget_mib": round(self.budget_bytes / (1024 * 1024), 3),
                "n_kernels": len(self.entries),
                "n_over_budget": sum(1 for e in self.entries
                                     if e["over_budget"]),
                "kernels": sorted(self.entries,
                                  key=lambda e: -int(e["vmem_bytes"])),
            }
            with open(self.report_path, "w") as f:
                json.dump(report, f, indent=2)
                f.write("\n")
        return []


def _prod(shape: Sequence[int]) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out
