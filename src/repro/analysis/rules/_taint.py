"""Shared per-function scan machinery for the rules.

``FnScanner`` walks ONE function body in statement order (never entering
nested defs — each def gets its own scanner run, so findings land on the
innermost function) while tracking a *traced-value taint* set:

* seed: the function's non-static parameters (for traced functions);
  static = ``static_argnames`` + keyword-only params (repo convention);
* propagate through assignments: a name assigned from a tainted
  expression is tainted, a name reassigned from a static one is cleared;
* static extractors break the chain: ``len(...)``, ``range(...)``,
  ``.shape`` / ``.ndim`` / ``.dtype`` / ``.size`` are concrete Python
  values *at trace time* even when applied to tracers — without this,
  every ``for i in range(len(params))`` would be a false positive.

Loop bodies can be scanned twice (``LOOP_PASSES = 2``) so loop-carried
hazards — a key consumed each iteration without resplitting, a buffer
donated in iteration *i* and passed again in *i+1* — surface on the
second pass.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

# attribute reads that yield static Python values even on tracers
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize", "nbytes"}
# calls that yield static Python values regardless of their arguments
STATIC_CALLS = {"len", "range", "isinstance", "hasattr", "getattr", "type",
                "str", "repr", "id", "callable",
                # jax's queries of the runtime answer in Python values
                "jax.default_backend", "jax.devices", "jax.device_count"}
# host-library namespaces: their results live on the host (R001's problem,
# not taint's — don't keep propagating device taint through them)
HOST_PREFIXES = ("numpy.", "math.", "scipy.")


def stmt_exprs(s: ast.stmt) -> List[ast.expr]:
    """The expressions belonging to the statement ITSELF (headers only
    for compound statements; bodies are walked as their own statements)."""
    if isinstance(s, ast.Assign):
        return [s.value] + list(s.targets)
    if isinstance(s, ast.AnnAssign):
        return [x for x in (s.value, s.target) if x is not None]
    if isinstance(s, ast.AugAssign):
        return [s.value, s.target]
    if isinstance(s, ast.Expr):
        return [s.value]
    if isinstance(s, ast.Return):
        return [s.value] if s.value is not None else []
    if isinstance(s, (ast.If, ast.While)):
        return [s.test]
    if isinstance(s, (ast.For, ast.AsyncFor)):
        return [s.iter]
    if isinstance(s, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in s.items]
    if isinstance(s, ast.Assert):
        return [s.test] + ([s.msg] if s.msg else [])
    if isinstance(s, ast.Raise):
        return [x for x in (s.exc, s.cause) if x is not None]
    if isinstance(s, ast.Delete):
        return list(s.targets)
    return []


def walk_no_defs(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk that does not descend into nested function definitions
    (they are scanned by their own FuncInfo pass)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            stack.append(c)


class FnScanner:
    """Statement-ordered scan of one function with taint tracking.

    Subclasses override ``on_stmt`` (called once per statement, BEFORE
    the statement's own assignments update the taint environment — so a
    use-before-rebind in the same statement is seen with the old state)
    and append to ``self.findings``.
    """

    LOOP_PASSES = 1

    def __init__(self, project, mod, fi):
        self.project = project
        self.mod = mod
        self.fi = fi
        self.static = fi.effective_static()
        self.traced = (
            {n for n in fi.arg_names if n not in self.static}
            if fi.traced else set())
        self.findings: list = []

    # -- taint --------------------------------------------------------------

    def tainted(self, node) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return self.tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.tainted(node.value) or self.tainted(node.slice)
        if isinstance(node, ast.Call):
            d = self.mod.dotted(node.func)
            if d in STATIC_CALLS:
                return False
            if d and d.startswith(HOST_PREFIXES):
                return False
            if d and d.startswith("jax."):
                return True
            # resolved defs propagate their arguments' taint (a model
            # helper applied to static config yields a static value)
            target = self.project.resolve_ref(self.mod, node.func, self.fi)
            if target is not None:
                return (any(self.tainted(a) for a in node.args)
                        or any(self.tainted(k.value)
                               for k in node.keywords))
            return (self.tainted(node.func)
                    or any(self.tainted(a) for a in node.args)
                    or any(self.tainted(k.value) for k in node.keywords))
        if isinstance(node, ast.BinOp):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.BoolOp):
            return any(self.tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # identity checks (`x is None`) are static at trace time
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return (self.tainted(node.left)
                    or any(self.tainted(c) for c in node.comparators))
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.IfExp):
            return (self.tainted(node.test) or self.tainted(node.body)
                    or self.tainted(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return (any(self.tainted(k) for k in node.keys if k)
                    or any(self.tainted(v) for v in node.values))
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return any(self.tainted(g.iter) for g in node.generators)
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, (ast.JoinedStr, ast.FormattedValue)):
            return False
        if isinstance(node, ast.Slice):
            return any(self.tainted(x)
                       for x in (node.lower, node.upper, node.step) if x)
        return False

    # -- driving ------------------------------------------------------------

    def run(self) -> list:
        body = self.fi.node.body
        if not isinstance(body, list):      # lambda: body is an expression
            ret = ast.Return(value=body)
            ast.copy_location(ret, body)
            body = [ret]
        self._stmts(body)
        return self.findings

    def _stmts(self, stmts) -> None:
        for s in stmts:
            self._stmt(s)

    def _stmt(self, s) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return
        self.on_stmt(s)
        if isinstance(s, ast.Assign):
            self._assign(s.targets, self.tainted(s.value))
        elif isinstance(s, ast.AnnAssign) and s.value is not None:
            self._assign([s.target], self.tainted(s.value))
        elif isinstance(s, ast.AugAssign):
            if self.tainted(s.value):
                self._assign([s.target], True)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self._assign([s.target], self.tainted(s.iter))
            for _ in range(self.LOOP_PASSES):
                self._stmts(s.body)
            self._stmts(s.orelse)
            return
        elif isinstance(s, ast.While):
            for _ in range(self.LOOP_PASSES):
                self._stmts(s.body)
            self._stmts(s.orelse)
            return
        if isinstance(s, ast.If):
            # branches are mutually exclusive: analyze each from the same
            # entry state, then merge (a key consumed in the `if` arm was
            # NOT consumed on the `elif` path); a branch that terminates
            # (`if ...: return` dispatch chains) contributes nothing to
            # the fall-through state
            entry = self.fork_state()
            self._stmts(s.body)
            after_body = self.fork_state()
            self.restore_state(entry)
            self._stmts(s.orelse)
            body_term = _terminates(s.body)
            orelse_term = _terminates(s.orelse)
            if body_term and orelse_term:
                self.restore_state(entry)
            elif orelse_term:
                self.restore_state(after_body)
            elif not body_term:
                self.merge_state(after_body)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            self._stmts(s.body)
        elif isinstance(s, ast.Try):
            self._stmts(s.body)
            for h in s.handlers:
                self._stmts(h.body)
            self._stmts(s.orelse)
            self._stmts(s.finalbody)

    def _assign(self, targets, is_tainted: bool) -> None:
        for name in assigned_names(targets):
            if is_tainted:
                self.traced.add(name)
            else:
                self.traced.discard(name)
            self.on_rebind(name)

    # -- subclass hooks -----------------------------------------------------

    def on_stmt(self, s) -> None:            # pragma: no cover - interface
        pass

    def on_rebind(self, name: str) -> None:  # pragma: no cover - interface
        pass

    # branch-state fork/merge: base tracks the taint set; subclasses with
    # extra flow state (donated buffers, consumed keys) extend all three
    def fork_state(self):
        return {"traced": set(self.traced)}

    def restore_state(self, state) -> None:
        self.traced = set(state["traced"])

    def merge_state(self, other) -> None:
        self.traced |= other["traced"]


def _terminates(stmts) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


def assigned_names(targets) -> List[str]:
    out: List[str] = []
    stack = list(targets)
    while stack:
        t = stack.pop()
        if isinstance(t, ast.Name):
            out.append(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
    return out
