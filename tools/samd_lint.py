#!/usr/bin/env python
"""samd-lint: structural contract checker for the repo's Pallas kernels.

AST + config driven. Walks every ``pl.pallas_call`` site in the given
paths and enforces the blocked-kernel invariants that the PR 6 kernels
rely on but nothing machine-checks:

  SL001 index-map-arity     every BlockSpec index map takes exactly
                            len(grid) arguments, plus
                            ``num_scalar_prefetch`` for
                            PrefetchScalarGridSpec kernels.
  SL002 index-map-offset    index maps return BLOCK indices; multiplying
                            a grid argument by anything inside the map is
                            the classic block/element unit error and is
                            rejected.
  SL003 ragged-k-padding    a kernel that accumulates across grid steps
                            (``scratch_shapes`` present) over a
                            ``pl.cdiv`` grid dimension MUST zero-pad its
                            operands to whole blocks (the PR 2 rule —
                            Mosaic block loads beyond the array edge are
                            garbage, and a carry accumulator folds the
                            garbage in). The enclosing function must call
                            a ``_pad_*`` helper, or be listed in
                            ``sl003_exempt`` (kernels that mask ragged
                            tails with ``pl.when`` instead, e.g. the
                            paged-attention page loop).
  SL004 vmem-budget         estimated VMEM scratch bytes (shape symbols
                            bound from ``symbols`` in the config —
                            ladder-maximum block sizes) must fit the
                            per-backend limit from
                            ``repro.analysis.contracts.VMEM_LIMIT_BYTES``.
  SL005 signed-wide-read    every call to ``unpack_lanes_wide`` must sit
                            in a function that also applies
                            ``correct_signed_product`` (or be
                            ``unpack_signed_product`` itself): a raw wide
                            read of a signed product silently returns
                            values off by one in lanes above negative
                            lanes (paper §6 / Fig. 12).

Run:  python tools/samd_lint.py src benchmarks [--json]
          [--config cfg.json] [--certify BENCH_serving.json]

``--certify`` additionally runs the repo-wide lane-safety certification
sweep (:mod:`repro.analysis.certify`) and folds unsafe configurations in
as CERT001 violations — the CI job runs both.

Exit status: 0 clean, 1 violations, 2 usage/internal error.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "src"))
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

# Config: symbol bindings are the LADDER-MAXIMUM block sizes (the largest
# values benchmarks/hillclimb.py will ever time), so the SL004 estimate
# upper-bounds every shipped configuration.
DEFAULT_CONFIG = {
    "symbols": {
        "bm": 256, "bn": 512, "bkw": 256,  # samd_matmul ladder max
        "blk": 4096,                        # samd_conv_chunks block
        "ow": 226, "wp": 226,               # VGG-B 224 + 2*padding
        "bc": 1024, "bcw": 128, "vpw": 16,  # conv channel block
        "bh": 8, "g": 32, "dh": 256, "sq": 8,  # paged attention
        "page_size": 16,
        # folded rows: rows = sq * g, planes * lanes = bh * dh
        "rows": 256, "planes": 1, "lanes": 2048,
    },
    "dtype_bytes": {
        "float32": 4, "int32": 4, "uint32": 4,
        "bfloat16": 2, "float16": 2, "int8": 1, "uint8": 1,
    },
    # (path-suffix, function) pairs whose ragged grid tail is handled by
    # in-kernel masking (pl.when on the page/position bound) rather than
    # operand zero-padding.
    "sl003_exempt": [],
    "vmem_backend": "tpu",
}


@dataclasses.dataclass
class Violation:
    rule: str
    path: str
    line: int
    func: str
    message: str

    def to_dict(self):
        return dataclasses.asdict(self)

    def __str__(self):
        return (
            f"{self.path}:{self.line}: {self.rule} [{self.func}] "
            f"{self.message}"
        )


def _attr_name(node: ast.AST) -> str:
    """Trailing attribute name: pl.pallas_call -> 'pallas_call'."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _call_names(tree: ast.AST) -> set[str]:
    return {
        _attr_name(n.func)
        for n in ast.walk(tree)
        if isinstance(n, ast.Call)
    }


class _SafeEval(Exception):
    pass


def _eval(node: ast.AST, env: dict[str, int]):
    """Tiny integer evaluator for shape expressions (SL004)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _SafeEval(node.id)
    if isinstance(node, ast.Tuple):
        return tuple(_eval(e, env) for e in node.elts)
    if isinstance(node, ast.BinOp):
        a, b = _eval(node.left, env), _eval(node.right, env)
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            return a // b
        raise _SafeEval(ast.dump(node.op))
    if isinstance(node, ast.Call) and _attr_name(node.func) == "cdiv":
        a, b = (_eval(x, env) for x in node.args)
        return -(-a // b)
    raise _SafeEval(ast.dump(node))


class _FileLint:
    def __init__(self, path: Path, tree: ast.Module, config: dict):
        self.path = path
        self.tree = tree
        self.config = config
        self.violations: list[Violation] = []
        self.notes: list[str] = []
        self._parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    def emit(self, rule: str, node: ast.AST, func: str, msg: str):
        self.violations.append(
            Violation(
                rule, str(self.path), getattr(node, "lineno", 0),
                func, msg,
            )
        )

    def enclosing_function(self, node: ast.AST):
        cur = self._parents.get(node)
        while cur is not None and not isinstance(
            cur, (ast.FunctionDef, ast.AsyncFunctionDef)
        ):
            cur = self._parents.get(cur)
        return cur

    # -- scope-local name resolution -----------------------------------
    def _assignments(self, scope: ast.AST, name: str) -> list[ast.AST]:
        """Every value ever assigned to ``name`` inside ``scope`` (if/else
        branches both count — the lint checks all of them)."""
        vals = []
        for n in ast.walk(scope):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        vals.append(n.value)
            elif isinstance(n, ast.AugAssign):
                if (
                    isinstance(n.target, ast.Name)
                    and n.target.id == name
                ):
                    vals.append(n.value)
        return vals

    def _resolve(self, node: ast.AST, scope: ast.AST) -> list[ast.AST]:
        """Flatten an in_specs/out_specs expression into BlockSpec-ish
        element expressions, chasing Name assignments, list literals,
        comprehensions and ``a + [b]`` concatenation."""
        if isinstance(node, (ast.List, ast.Tuple)):
            out = []
            for e in node.elts:
                out.extend(self._resolve(e, scope))
            return out
        if isinstance(node, ast.Name):
            out = []
            for v in self._assignments(scope, node.id):
                out.extend(self._resolve(v, scope))
            return out
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return self._resolve(node.left, scope) + self._resolve(
                node.right, scope
            )
        if isinstance(node, ast.ListComp):
            return self._resolve(node.elt, scope)
        return [node]

    def _grid_tuple(self, node: ast.AST, scope) -> ast.Tuple | None:
        if isinstance(node, ast.Tuple):
            return node
        if isinstance(node, ast.Name):
            for v in self._assignments(scope, node.id):
                if isinstance(v, ast.Tuple):
                    return v
        return None

    def _index_map_arity(self, node: ast.AST, scope):
        """(n_args, map_node) for a lambda / named def / partial-wrapped
        lambda index map; None when unresolvable."""
        if isinstance(node, ast.Lambda):
            return len(node.args.args), node
        if isinstance(node, ast.Name):
            for n in ast.walk(scope):
                if (
                    isinstance(n, ast.FunctionDef)
                    and n.name == node.id
                ):
                    return len(n.args.args), n
            return None
        if (
            isinstance(node, ast.Call)
            and _attr_name(node.func) == "partial"
            and node.args
        ):
            inner = self._index_map_arity(node.args[0], scope)
            if inner is None:
                return None
            n_args, map_node = inner
            return n_args - len(node.keywords), map_node
        return None

    # -- rules ---------------------------------------------------------
    def check_pallas_call(self, call: ast.Call):
        scope = self.enclosing_function(call) or self.tree
        fname = getattr(scope, "name", "<module>")
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        prefetch = 0
        if "grid_spec" in kw and isinstance(kw["grid_spec"], ast.Call):
            spec_kw = {
                k.arg: k.value
                for k in kw["grid_spec"].keywords
                if k.arg
            }
            nsp = spec_kw.get("num_scalar_prefetch")
            if isinstance(nsp, ast.Constant):
                prefetch = int(nsp.value)
            kw = {**spec_kw, **{
                k: v for k, v in kw.items() if k != "grid_spec"
            }}
        grid_expr = kw.get("grid")
        if grid_expr is None:
            return
        grid = self._grid_tuple(grid_expr, scope)

        specs = []
        for key in ("in_specs", "out_specs"):
            if key in kw:
                specs.extend(self._resolve(kw[key], scope))
        index_maps = []
        for spec in specs:
            if (
                isinstance(spec, ast.Call)
                and _attr_name(spec.func) == "BlockSpec"
                and len(spec.args) >= 2
            ):
                index_maps.append(spec.args[1])

        # SL001: index-map arity = grid rank + scalar-prefetch operands
        if grid is not None:
            expect = len(grid.elts) + prefetch
            for m in index_maps:
                got = self._index_map_arity(m, scope)
                if got is None:
                    self.notes.append(
                        f"{self.path}:{m.lineno}: SL001 skipped "
                        f"(unresolvable index map in {fname})"
                    )
                    continue
                n_args, _ = got
                if n_args != expect:
                    self.emit(
                        "SL001", m, fname,
                        f"index map takes {n_args} args, grid rank "
                        f"{len(grid.elts)} + {prefetch} prefetch "
                        f"operands requires {expect}",
                    )

        # SL002: no multiplication of a map argument inside the map body
        for m in index_maps:
            got = self._index_map_arity(m, scope)
            if got is None:
                continue
            _, map_node = got
            params = {
                a.arg
                for a in map_node.args.args
            }
            body = (
                map_node.body
                if isinstance(map_node, ast.Lambda)
                else map_node
            )
            for n in ast.walk(body):
                if isinstance(n, ast.BinOp) and isinstance(
                    n.op, ast.Mult
                ):
                    names = {
                        c.id
                        for side in (n.left, n.right)
                        for c in ast.walk(side)
                        if isinstance(c, ast.Name)
                    }
                    if names & params:
                        self.emit(
                            "SL002", n, fname,
                            "index map multiplies a grid argument — "
                            "maps return BLOCK indices, not element "
                            "offsets (Pallas scales by block_shape)",
                        )

        # SL003: cdiv grid + cross-step scratch accumulator => zero-pad
        has_scratch = "scratch_shapes" in kw
        grid_elts = grid.elts if grid is not None else [grid_expr]
        ragged = any(
            isinstance(n, ast.Call) and _attr_name(n.func) == "cdiv"
            for e in grid_elts
            for n in ast.walk(e)
        )
        if ragged and has_scratch:
            exempt = any(
                str(self.path).endswith(p) and fname == f
                for p, f in map(tuple, self.config["sl003_exempt"])
            )
            calls = _call_names(scope)
            pads = {c for c in calls if c.startswith("_pad_")}
            if not pads and not exempt:
                self.emit(
                    "SL003", call, fname,
                    "pl.cdiv grid with a cross-step scratch "
                    "accumulator but no _pad_* operand zero-padding "
                    "(PR 2 rule): a ragged tail block reads garbage "
                    "into the carried accumulator",
                )

        # SL004: scratch VMEM estimate vs per-backend budget
        if has_scratch:
            self._check_vmem(kw["scratch_shapes"], scope, fname, call)

    def _check_vmem(self, scratch_expr, scope, fname, call):
        from repro.analysis.contracts import vmem_limit

        env = dict(self.config["symbols"])
        dtype_bytes = self.config["dtype_bytes"]
        total = 0
        for entry in self._resolve(scratch_expr, scope):
            if not (
                isinstance(entry, ast.Call)
                and _attr_name(entry.func) == "VMEM"
                and len(entry.args) >= 2
            ):
                continue
            try:
                shape = _eval(entry.args[0], env)
            except _SafeEval as e:
                self.notes.append(
                    f"{self.path}:{entry.lineno}: SL004 skipped a "
                    f"scratch entry in {fname} (unbound symbol {e}; "
                    "add it to the lint config symbols)"
                )
                continue
            dt = _attr_name(entry.args[1])
            nbytes = dtype_bytes.get(dt, 4)
            n = 1
            for d in shape if isinstance(shape, tuple) else (shape,):
                n *= int(d)
            total += n * nbytes
        limit = vmem_limit(self.config["vmem_backend"])
        if total > limit:
            self.emit(
                "SL004", call, fname,
                f"estimated VMEM scratch {total} bytes exceeds the "
                f"{self.config['vmem_backend']} budget {limit} at "
                "ladder-maximum block sizes",
            )

    def check_signed_wide_reads(self):
        for n in ast.walk(self.tree):
            if not (
                isinstance(n, ast.Call)
                and _attr_name(n.func) == "unpack_lanes_wide"
            ):
                continue
            scope = self.enclosing_function(n)
            fname = getattr(scope, "name", "<module>")
            fixed = scope is not None and (
                "correct_signed_product" in _call_names(scope)
            )
            if not fixed:
                self.emit(
                    "SL005", n, fname,
                    "raw unpack_lanes_wide without "
                    "correct_signed_product in scope — signed product "
                    "lanes above a negative lane read off-by-one "
                    "(Fig. 12); route through unpack_signed_product",
                )

    def run(self):
        for n in ast.walk(self.tree):
            if (
                isinstance(n, ast.Call)
                and _attr_name(n.func) == "pallas_call"
            ):
                self.check_pallas_call(n)
        self.check_signed_wide_reads()
        return self.violations, self.notes


def lint_paths(paths: list[Path], config: dict):
    violations, notes = [], []
    files = []
    for p in paths:
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        else:
            files.append(p)
    for f in files:
        try:
            tree = ast.parse(f.read_text(), filename=str(f))
        except SyntaxError as e:
            violations.append(
                Violation("SL000", str(f), e.lineno or 0, "<parse>",
                          f"syntax error: {e.msg}")
            )
            continue
        v, n = _FileLint(f, tree, config).run()
        violations.extend(v)
        notes.extend(n)
    return violations, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="SAMD Pallas kernel contract lint"
    )
    ap.add_argument("paths", nargs="*", type=Path,
                    default=[Path("src"), Path("benchmarks")])
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--config", type=Path, default=None,
                    help="JSON overriding DEFAULT_CONFIG keys")
    ap.add_argument(
        "--certify", type=Path, metavar="BENCH_JSON", default=None,
        help="also run the repro.analysis.certify sweep against this "
             "serving artifact",
    )
    args = ap.parse_args(argv)

    config = dict(DEFAULT_CONFIG)
    if args.config:
        config.update(json.loads(args.config.read_text()))

    violations, notes = lint_paths(args.paths or None, config)

    if args.certify is not None:
        from repro.analysis import certify

        entries, _ = certify.run(args.certify)
        for e in entries:
            if e["status"] != "safe":
                violations.append(
                    Violation("CERT001", str(args.certify), 0,
                              e["config"], e["detail"] or e["status"])
                )
        notes.append(
            f"certify: {len(entries)} configurations checked"
        )

    if args.json:
        json.dump(
            {
                "violations": [v.to_dict() for v in violations],
                "notes": notes,
            },
            sys.stdout, indent=1,
        )
        print()
    else:
        for v in violations:
            print(v)
        for n in notes:
            print(f"note: {n}", file=sys.stderr)
        print(
            f"samd-lint: {len(violations)} violation(s)",
            file=sys.stderr,
        )
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
