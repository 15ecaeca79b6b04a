"""off-path-guards: telemetry on worker hot paths stays a None test.

Every observability hook on the storage/TPU hot path — ``--tracefile``
spans, the per-phase stage spans, ``--slowops`` capture — is wired as a
nullable handle (``self._tracer`` / ``self._stages`` / ``ctx.stages`` /
``self._slowops``): when the
feature is off the handle is None and the instrumentation must compile
down to ONE ``x is None`` attribute test, never a call or attribute
chain. This rule finds handle uses (any dotted chain *through* a
handle, e.g. ``self._tracer.record_op(...)``) that are not lexically
dominated by an ``is not None`` guard on that exact expression.

Accepted guard idioms (all used in the tree):

- ``if self._tracer is not None: ...``   (and-chains included)
- ``if x is None: ... else: <use>`` and early-outs
  (``if x is None: return``)
- conditional expressions: ``t.now_ns() if t is not None else 0``
- aliases: ``tracer = getattr(worker, "_tracer", None)`` followed by
  ``if tracer is not None:`` — the alias inherits handle-ness

Truthiness guards (``if self._tracer:``) are deliberately NOT accepted:
the documented idiom is the identity test, which can never call a
``__bool__``.
"""

from __future__ import annotations

import ast

from .core import Finding, dotted_name, parent, rule

#: attribute names that carry a nullable telemetry handle
HANDLE_ATTRS = frozenset({"_tracer", "_slowops", "tracer", "_stages",
                          "stages"})

#: the worker hot-path modules this rule patrols
HOT_PATH_DIRS = ("elbencho_tpu/workers", "elbencho_tpu/tpu")


def _guarded_names(test: ast.AST, positive: bool) -> "set[str]":
    """Dotted expressions asserted non-None when `test` evaluates
    truthy (positive=True) or falsy (positive=False)."""
    out: "set[str]" = set()

    def visit(t, pos):
        if isinstance(t, ast.BoolOp) and isinstance(t.op, ast.And) \
                and pos:
            for v in t.values:
                visit(v, pos)
        elif isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
            visit(t.operand, not pos)
        elif isinstance(t, ast.Compare) and len(t.ops) == 1 \
                and isinstance(t.comparators[0], ast.Constant) \
                and t.comparators[0].value is None:
            is_not = isinstance(t.ops[0], ast.IsNot)
            is_ = isinstance(t.ops[0], ast.Is)
            if (is_not and pos) or (is_ and not pos):
                d = dotted_name(t.left)
                if d:
                    out.add(d)

    visit(test, positive)
    return out


def _is_early_out(stmt: ast.stmt) -> "set[str]":
    """``if x is None: return/raise/continue`` — names guarded for every
    following sibling statement."""
    if not isinstance(stmt, ast.If) or stmt.orelse:
        return set()
    if not all(isinstance(b, (ast.Return, ast.Raise, ast.Continue,
                              ast.Break))
               for b in stmt.body):
        return set()
    return _guarded_names(stmt.test, positive=False)


def _stmt_block_chain(node: ast.AST):
    """(owner, block, stmt) for every statement list containing an
    ancestor of node, innermost first."""
    n = node
    while True:
        p = parent(n)
        if p is None:
            return
        for fname in ("body", "orelse", "finalbody"):
            block = getattr(p, fname, None)
            if isinstance(block, list) and n in block:
                yield p, fname, block, n
        n = p


def _is_guarded(node: ast.AST, expr: str) -> bool:
    # enclosing if / ternary guards
    n = node
    while True:
        p = parent(n)
        if p is None:
            break
        if isinstance(p, ast.If):
            if n in p.body and expr in _guarded_names(p.test, True):
                return True
            if n in p.orelse and expr in _guarded_names(p.test, False):
                return True
        if isinstance(p, ast.IfExp):
            if n is p.body and expr in _guarded_names(p.test, True):
                return True
            if n is p.orelse and expr in _guarded_names(p.test, False):
                return True
        if isinstance(p, ast.BoolOp) and isinstance(p.op, ast.And):
            idx = p.values.index(n) if n in p.values else -1
            for prior in p.values[:max(idx, 0)]:
                if expr in _guarded_names(prior, True):
                    return True
        n = p
    # early-out guards in any enclosing block, before our statement
    for _owner, _fname, block, stmt in _stmt_block_chain(node):
        for prev in block[:block.index(stmt)]:
            if expr in _is_early_out(prev):
                return True
    return False


def _function_aliases(func: ast.AST) -> "set[str]":
    """Local names assigned from a handle attribute or from
    ``getattr(x, "_tracer", None)`` — they carry handle-ness."""
    out: "set[str]" = set()
    for node in ast.walk(func):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1 \
                or not isinstance(node.targets[0], ast.Name):
            continue
        val = node.value
        if isinstance(val, ast.Attribute) and val.attr in HANDLE_ATTRS:
            out.add(node.targets[0].id)
        elif isinstance(val, ast.Call) \
                and isinstance(val.func, ast.Name) \
                and val.func.id == "getattr" and len(val.args) >= 2 \
                and isinstance(val.args[1], ast.Constant) \
                and val.args[1].value in HANDLE_ATTRS:
            out.add(node.targets[0].id)
    return out


def check_file(project, rel: str) -> "list[Finding]":
    tree = project.tree(rel)
    if tree is None:
        return []
    out: "list[Finding]" = []
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        aliases = _function_aliases(func)
        for node in ast.walk(func):
            if node is func:
                continue
            # uses THROUGH a handle: Attribute whose base expression is
            # a handle chain or alias
            if not isinstance(node, ast.Attribute):
                continue
            base = dotted_name(node.value)
            if base is None:
                continue
            last = base.rsplit(".", 1)[-1]
            if last in HANDLE_ATTRS or base in aliases:
                # don't double-report each link of one chain: only the
                # innermost attribute directly on the handle
                if _is_guarded(node, base):
                    continue
                func_label = func.name
                out.append(Finding(
                    "off-path-guards", rel, node.lineno,
                    f"{func_label}:{base}.{node.attr}",
                    f"`{base}.{node.attr}` runs without an `is not "
                    f"None` guard on `{base}` — off-path telemetry "
                    f"must stay a single None test when the feature "
                    f"is off (guard the block, or alias + guard)"))
    return out


@rule("off-path-guards",
      "telemetry/tracer/slowops hooks on worker hot paths compile to a "
      "single `x is None` attribute test when the feature is off")
def check(project) -> "list[Finding]":
    out: "list[Finding]" = []
    for rel in project.py_files():
        if any(rel.startswith(d + "/") or rel.startswith(d.replace(
                "/", "\\") + "\\") for d in HOT_PATH_DIRS):
            out.extend(check_file(project, rel))
    return out
