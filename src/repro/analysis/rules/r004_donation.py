"""R004 — donated buffer reused after a ``donate_argnums`` call.

The lane engine's epoch program donates its carry (params, Adam state
and early-stop state) into each call so XLA can update in place.  Reading
the donated Python name afterwards hits a deleted buffer
(``RuntimeError: Array has been deleted``) — or, in a loop, passes a dead
buffer back into the next iteration.  The fix is the engine's own idiom:
rebind the name from the call's results
(``carry, ... = run_epoch(carry, ...)``).

Tracked donors: defs with ``donate_argnums`` (decorator or ``jax.jit``
call site) and variables holding the result of the known donating engine
factory (``get_lanes_engine``, the epoch program's).
Loop bodies are scanned twice so iteration-carried reuse surfaces.
"""
from __future__ import annotations

import ast

from repro.analysis.lint import DONATING_FACTORIES, Finding
from repro.analysis.rules._taint import (FnScanner, assigned_names,
                                         stmt_exprs, walk_no_defs)

RULE = "R004"
TITLE = "donated buffer reused after donate_argnums call"
HINT = ("rebind the name from the call's results "
        "(`params, opt_state, ... = step(params, opt_state, ...)`); a "
        "donated buffer is deleted on dispatch")


class _Scanner(FnScanner):

    LOOP_PASSES = 2

    def __init__(self, project, mod, fi):
        super().__init__(project, mod, fi)
        self.donated = {}      # var name -> line where it was donated
        self.engines = {}      # var name -> donate positions of its callee
        self._reported = set()

    def on_stmt(self, s):
        exprs = stmt_exprs(s)
        # 1) uses of already-donated names (old state — before this
        #    statement's own rebinds clear anything)
        for expr in exprs:
            for node in walk_no_defs(expr):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in self.donated:
                    key = (node.id, node.lineno)
                    if key not in self._reported:
                        self._reported.add(key)
                        self.findings.append(Finding(
                            rule=RULE, file=self.mod.relpath,
                            line=node.lineno, symbol=self.fi.qualname,
                            message=f"`{node.id}` used after being donated "
                                    f"at line {self.donated[node.id]}",
                            hint=HINT, code=self.mod.code_line(node)))
        # 2) new donations in this statement
        for expr in exprs:
            for node in walk_no_defs(expr):
                if isinstance(node, ast.Call):
                    self._maybe_donate(node)
        # 3) engine-factory bindings (`eng = get_lanes_engine(...)`)
        if isinstance(s, ast.Assign) and isinstance(s.value, ast.Call):
            d = self.mod.dotted(s.value.func)
            positions = DONATING_FACTORIES.get(d) or DONATING_FACTORIES.get(
                f"{self.mod.modpath}.{d}" if d else "")
            if positions:
                for name in assigned_names(s.targets):
                    self.engines[name] = positions

    def _maybe_donate(self, call):
        positions = None
        if isinstance(call.func, ast.Name) and call.func.id in self.engines:
            positions = self.engines[call.func.id]
        else:
            target = self.project.resolve_ref(self.mod, call.func, self.fi)
            if target is not None and target.donate_argnums:
                positions = target.donate_argnums
        if not positions:
            return
        for i in positions:
            if i < len(call.args) and isinstance(call.args[i], ast.Name):
                self.donated[call.args[i].id] = call.lineno

    def on_rebind(self, name):
        self.donated.pop(name, None)
        self.engines.pop(name, None)

    def fork_state(self):
        state = super().fork_state()
        state["donated"] = dict(self.donated)
        state["engines"] = dict(self.engines)
        return state

    def restore_state(self, state):
        super().restore_state(state)
        self.donated = dict(state["donated"])
        self.engines = dict(state["engines"])

    def merge_state(self, other):
        super().merge_state(other)
        self.donated.update(other["donated"])
        self.engines.update(other["engines"])


def check(project):
    out = []
    for mod, fi in project.all_functions():
        out.extend(_Scanner(project, mod, fi).run())
    return out
