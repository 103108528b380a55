"""jaxlint static layer: an AST index of the package that knows which
function bodies run *traced* (inside ``jax.jit`` / ``vmap`` / ``lax.scan``
/ ``pallas_call``), plus the lint driver and the baseline machinery.

Why an index and not per-file regexes: every rule that matters here is a
property of *traced* code ("no ``np.*`` inside a jitted body", "no Python
``if`` on a traced value"), and tracedness is non-local — a loss function
defined in ``core/autoencoder.py`` is traced because ``core/training.py``
closes a jitted scan over it.  So the linter parses the whole package
once, marks traced roots, and propagates tracedness across modules
through resolvable references before any rule runs:

* **roots** — defs decorated/wrapped with a tracing transform
  (``jax.jit``, ``partial(jax.jit, ...)``, ``jax.vmap``, ``jax.grad``,
  ``jax.custom_vjp``), defs passed as arguments to a tracing call
  (``lax.scan``/``cond``/``while_loop``/``switch``, ``pl.pallas_call``,
  ``jax.jit(self._impl)``), defs passed to the training engine's
  loss-consuming entry points (``training.train*`` / ``get_lanes*engine``),
  and — by repo convention — defs named ``loss`` / ``*_loss`` (losses are
  always consumed by a jitted engine);
* **propagation** — a def lexically nested in a traced def is traced; any
  function *referenced* inside a traced body is traced, resolved through
  each module's import aliases (``ae.encode`` in a traced loss marks
  ``repro.core.autoencoder.encode``), iterated to a fixpoint.

Staticness convention: names listed in a jit's ``static_argnames`` (or
``static_argnums``) and **keyword-only parameters** are treated as static
Python values — the repo-wide idiom for hyperparameters threaded into
jitted/Pallas code — so branching on them is legal (R002) and converting
them with ``float()``/``int()`` is legal (R001).

The baseline (``analysis/baseline.json``) freezes pre-existing debt by
fingerprint ``(rule, file, symbol, code-line)`` — line *numbers* are not
part of the identity, so unrelated edits don't churn it — and every entry
carries a one-line justification.  New violations (fingerprints not in
the baseline, or more occurrences than the baseline count) fail the lint.
"""
from __future__ import annotations

import ast
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# what counts as "enters a trace"
# ---------------------------------------------------------------------------

# transforms whose function-valued arguments run traced
TRACING_CALLS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.jacfwd", "jax.jacrev", "jax.hessian", "jax.checkpoint",
    "jax.remat", "jax.custom_vjp", "jax.custom_jvp", "jax.closure_convert",
    "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop", "jax.lax.switch",
    "jax.lax.map", "jax.lax.fori_loop", "jax.lax.associative_scan",
    "jax.lax.custom_root", "jax.experimental.pallas.pallas_call",
    "jax.experimental.shard_map.shard_map",
}

# repo entry points that trace their function-valued arguments (the loss):
# the engine contract of repro.core.training
ENGINE_CALLS = {
    f"repro.core.training.{name}" for name in (
        "train", "train_lanes", "train_lanes_epochwise", "get_lanes_engine",
        "get_lanes_fit_engine")
}

# defs with these names are traced by convention: losses are consumed by
# the jitted engines even when no call site is statically resolvable.
# Factory prefixes are excluded (make_loss BUILDS a loss on the host).
LOSS_NAME_SUFFIX = "loss"
FACTORY_PREFIXES = ("make_", "build_", "get_", "create_")

# parameters that are static Python config by repo convention, either by
# name or by scalar annotation (see analysis/README.md "conventions")
STATIC_PARAM_NAMES = {"cfg", "config", "spec", "hp", "mesh", "mesh_axes"}
STATIC_ANNOTATIONS = {"int", "str", "bool", "float"}

# factory functions whose RETURN VALUE is a jitted callable donating these
# positional argument indices (R004 tracks variables assigned from them)
DONATING_FACTORIES = {
    "repro.core.training.get_lanes_engine": (0,),
}


# ---------------------------------------------------------------------------
# findings + baseline
# ---------------------------------------------------------------------------

@dataclass
class Finding:
    rule: str                 # "R001"..."R007"
    file: str                 # repo-relative path
    line: int                 # 1-indexed
    symbol: str               # enclosing function qualname ("" = module)
    message: str
    hint: str = ""
    code: str = ""            # stripped source line (fingerprint component)

    @property
    def fingerprint(self) -> Tuple[str, str, str, str]:
        return (self.rule, self.file, self.symbol, self.code)

    def to_dict(self) -> dict:
        return {"rule": self.rule, "file": self.file, "line": self.line,
                "symbol": self.symbol, "message": self.message,
                "hint": self.hint, "code": self.code}


def load_baseline(path: str) -> Dict[Tuple[str, str, str, str], int]:
    """fingerprint -> allowed occurrence count."""
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        data = json.load(fh)
    out: Dict[Tuple[str, str, str, str], int] = {}
    for e in data.get("entries", []):
        fp = (e["rule"], e["file"], e["symbol"], e["code"])
        out[fp] = out.get(fp, 0) + int(e.get("count", 1))
    return out


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    """Freeze ``findings`` as the new baseline, keeping the justification
    of any entry whose fingerprint survives."""
    old = {}
    if os.path.exists(path):
        with open(path) as fh:
            for e in json.load(fh).get("entries", []):
                old[(e["rule"], e["file"], e["symbol"], e["code"])] = \
                    e.get("justification", "")
    counts: Dict[Tuple[str, str, str, str], Finding] = {}
    n: Dict[Tuple[str, str, str, str], int] = {}
    for f in findings:
        counts.setdefault(f.fingerprint, f)
        n[f.fingerprint] = n.get(f.fingerprint, 0) + 1
    entries = []
    for fp, f in sorted(counts.items()):
        entries.append({
            "rule": f.rule, "file": f.file, "symbol": f.symbol,
            "code": f.code, "count": n[fp],
            "justification": old.get(fp, "TODO: justify or fix"),
        })
    with open(path, "w") as fh:
        json.dump({"_": "jaxlint baseline: frozen pre-existing findings "
                        "(see analysis/README.md); regenerate with "
                        "python -m repro.launch.lint --baseline-update",
                   "entries": entries}, fh, indent=1)
        fh.write("\n")


def apply_baseline(findings: Sequence[Finding],
                   baseline: Dict[Tuple[str, str, str, str], int]
                   ) -> List[Finding]:
    """Drop findings covered by the baseline; occurrences beyond an
    entry's count still fail (a NEW copy of an old sin is a new sin)."""
    budget = dict(baseline)
    out = []
    for f in findings:
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
        else:
            out.append(f)
    return out


# ---------------------------------------------------------------------------
# the module index
# ---------------------------------------------------------------------------

class FuncInfo:
    """One function/lambda definition and what the linter knows about it."""

    __slots__ = ("node", "module", "qualname", "parent", "class_name",
                 "traced", "traced_reason", "is_jit_root", "static_names",
                 "donate_argnums", "children")

    def __init__(self, node, module: "ModuleIndex", qualname: str,
                 parent: Optional["FuncInfo"], class_name: Optional[str]):
        self.node = node
        self.module = module
        self.qualname = qualname
        self.parent = parent
        self.class_name = class_name
        self.traced = False
        self.traced_reason = ""
        self.is_jit_root = False
        self.static_names: set = set()
        self.donate_argnums: Tuple[int, ...] = ()
        self.children: Dict[str, "FuncInfo"] = {}

    # -- parameters ---------------------------------------------------------

    @property
    def arg_names(self) -> List[str]:
        a = self.node.args
        return ([x.arg for x in getattr(a, "posonlyargs", [])]
                + [x.arg for x in a.args] + [x.arg for x in a.kwonlyargs])

    @property
    def kwonly_names(self) -> List[str]:
        return [x.arg for x in self.node.args.kwonlyargs]

    def conventional_static_params(self) -> set:
        """Params static by repo convention: keyword-only, named like
        config (``cfg`` etc.), or annotated with a Python scalar type
        (``pad: int``, ``kind: str`` — hyperparameters, not tracers)."""
        out = set(self.kwonly_names)
        a = self.node.args
        for arg in (list(getattr(a, "posonlyargs", [])) + list(a.args)
                    + list(a.kwonlyargs)):
            if arg.arg in STATIC_PARAM_NAMES:
                out.add(arg.arg)
            ann = arg.annotation
            if isinstance(ann, ast.Name) and ann.id in STATIC_ANNOTATIONS:
                out.add(arg.arg)
        return out

    def effective_static(self) -> set:
        """Static names visible in this body: own static_argnames +
        conventionally-static params, plus every ancestor's — nested defs
        close over the outer statics."""
        names, fi = set(), self
        while fi is not None:
            names |= fi.static_names
            names |= fi.conventional_static_params()
            fi = fi.parent
        return names

    def __repr__(self):
        return (f"<FuncInfo {self.module.modpath}:{self.qualname}"
                f"{' traced' if self.traced else ''}>")


class ModuleIndex(ast.NodeVisitor):
    """Parse one module: definitions, import aliases, source lines."""

    def __init__(self, abspath: str, relpath: str, modpath: str,
                 source: str):
        self.abspath = abspath
        self.relpath = relpath
        self.modpath = modpath          # e.g. "repro.core.training"
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=relpath)
        self.imports: Dict[str, str] = {}     # local alias -> dotted path
        self.funcs: Dict[ast.AST, FuncInfo] = {}
        self.top_names: Dict[str, FuncInfo] = {}
        self.methods: Dict[Tuple[str, str], FuncInfo] = {}
        self._func_stack: List[FuncInfo] = []
        self._class_stack: List[str] = []
        self.visit(self.tree)

    def code_line(self, node) -> str:
        try:
            return self.lines[node.lineno - 1].strip()
        except Exception:
            return ""

    # -- collection ---------------------------------------------------------

    def visit_Import(self, node):
        for a in node.names:
            self.imports[a.asname or a.name.split(".")[0]] = \
                a.name if a.asname else a.name.split(".")[0]

    def visit_ImportFrom(self, node):
        if node.level:                      # relative imports: not used here
            return
        for a in node.names:
            self.imports[a.asname or a.name] = f"{node.module}.{a.name}"

    def _enter_def(self, node, name: str):
        parent = self._func_stack[-1] if self._func_stack else None
        cls = self._class_stack[-1] if self._class_stack else None
        qual = f"{parent.qualname}.{name}" if parent else \
            (f"{cls}.{name}" if cls else name)
        fi = FuncInfo(node, self, qual, parent, cls)
        self.funcs[node] = fi
        if parent is not None:
            parent.children[name] = fi
        elif cls is not None:
            self.methods[(cls, name)] = fi
        else:
            self.top_names[name] = fi
        return fi

    def visit_FunctionDef(self, node):
        self._visit_def(node, node.name)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        fi = self._enter_def(node, "<lambda>")
        self._func_stack.append(fi)
        self.generic_visit(node)
        self._func_stack.pop()

    def _visit_def(self, node, name):
        fi = self._enter_def(node, name)
        self._func_stack.append(fi)
        self.generic_visit(node)
        self._func_stack.pop()

    def visit_ClassDef(self, node):
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    # -- name resolution ----------------------------------------------------

    def dotted(self, node) -> Optional[str]:
        """Resolve an expression to a dotted external path through the
        module's import aliases: ``jnp.mean`` -> ``jax.numpy.mean``,
        ``pl.pallas_call`` -> ``jax.experimental.pallas.pallas_call``,
        bare builtins to their own name.  None when unresolvable."""
        if isinstance(node, ast.Name):
            return self.imports.get(node.id, node.id)
        if isinstance(node, ast.Attribute):
            base = self.dotted(node.value)
            return f"{base}.{node.attr}" if base else None
        return None

    def resolve_local(self, node, scope: Optional[FuncInfo]
                      ) -> Optional[FuncInfo]:
        """Resolve a Name/Attribute to a def in THIS module: enclosing
        scopes' nested defs, module top level, or ``self.method``."""
        if isinstance(node, ast.Name):
            fi = scope
            while fi is not None:
                if node.id in fi.children:
                    return fi.children[node.id]
                fi = fi.parent
            return self.top_names.get(node.id)
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and node.value.id == "self":
            fi = scope
            while fi is not None and fi.class_name is None:
                fi = fi.parent
            if fi is not None:
                return self.methods.get((fi.class_name, node.attr))
        return None


# ---------------------------------------------------------------------------
# the project index (cross-module fixpoint)
# ---------------------------------------------------------------------------

class ProjectIndex:
    """All modules of a lint run, with tracedness propagated to fixpoint."""

    def __init__(self, modules: Dict[str, ModuleIndex]):
        self.modules = modules
        self._mark_roots()
        self._propagate()

    # -- helpers shared with the rules --------------------------------------

    def resolve_ref(self, mod: ModuleIndex, node,
                    scope: Optional[FuncInfo]) -> Optional[FuncInfo]:
        """Resolve a reference to a FuncInfo, same-module first, then
        cross-module through import aliases (``ae.encode``,
        ``from m import f``)."""
        fi = mod.resolve_local(node, scope)
        if fi is not None:
            return fi
        dotted = mod.dotted(node)
        if not dotted or "." not in dotted:
            return None
        modpath, name = dotted.rsplit(".", 1)
        target = self.modules.get(modpath)
        if target is not None:
            return target.top_names.get(name)
        return None

    def traced_functions(self) -> Iterable[Tuple[ModuleIndex, FuncInfo]]:
        for mod in self.modules.values():
            for fi in mod.funcs.values():
                if fi.traced:
                    yield mod, fi

    def all_functions(self) -> Iterable[Tuple[ModuleIndex, FuncInfo]]:
        for mod in self.modules.values():
            for fi in mod.funcs.values():
                yield mod, fi

    def own_body_nodes(self, fi: FuncInfo) -> Iterable[ast.AST]:
        """Walk a function's body WITHOUT descending into nested defs
        (each def is examined exactly once, findings attributed to the
        innermost function)."""
        body = fi.node.body
        stack = list(body) if isinstance(body, list) else [body]
        while stack:
            node = stack.pop()
            yield node
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue
                stack.append(child)

    # -- root marking -------------------------------------------------------

    def _jit_meta_from_call(self, mod: ModuleIndex, call: ast.Call,
                            fi: FuncInfo) -> None:
        """Record static_argnames/argnums + donate_argnums from a jit(...)
        or partial(jax.jit, ...) expression onto ``fi``."""
        fi.is_jit_root = True
        for kw in call.keywords:
            if kw.arg in ("static_argnames", "static_argnums",
                          "donate_argnums"):
                vals = []
                elts = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for e in elts:
                    if isinstance(e, ast.Constant):
                        vals.append(e.value)
                if kw.arg == "static_argnames":
                    fi.static_names |= {v for v in vals if isinstance(v, str)}
                elif kw.arg == "static_argnums":
                    names = fi.arg_names
                    for v in vals:
                        if isinstance(v, int) and v < len(names):
                            fi.static_names.add(names[v])
                else:
                    fi.donate_argnums = tuple(
                        v for v in vals if isinstance(v, int))

    def _mark_roots(self) -> None:
        self._worklist: List[FuncInfo] = []
        for mod in self.modules.values():
            for node, fi in mod.funcs.items():
                # convention: losses run inside the jitted engines
                # (factories like make_loss build one on the host — skip)
                name = fi.qualname.rsplit(".", 1)[-1]
                if (name == LOSS_NAME_SUFFIX
                        or name.endswith("_" + LOSS_NAME_SUFFIX)) and \
                        not name.startswith(FACTORY_PREFIXES):
                    self._mark(fi, "loss-name convention")
                # decorators
                for dec in getattr(node, "decorator_list", []):
                    d = mod.dotted(dec)
                    if d in TRACING_CALLS:
                        fi.is_jit_root = d == "jax.jit"
                        self._mark(fi, f"decorated @{d}")
                    elif isinstance(dec, ast.Call):
                        dc = mod.dotted(dec.func)
                        if dc in TRACING_CALLS:
                            if dc == "jax.jit":
                                self._jit_meta_from_call(mod, dec, fi)
                            self._mark(fi, f"decorated @{dc}(...)")
                        elif dc == "functools.partial" and dec.args and \
                                mod.dotted(dec.args[0]) in TRACING_CALLS:
                            if mod.dotted(dec.args[0]) == "jax.jit":
                                self._jit_meta_from_call(mod, dec, fi)
                            self._mark(fi, "decorated @partial(jit, ...)")
            # call sites: jax.jit(f) / lax.scan(f, ...) / train(_, loss)
            for call in ast.walk(mod.tree):
                if not isinstance(call, ast.Call):
                    continue
                d = mod.dotted(call.func)
                if d not in TRACING_CALLS and d not in ENGINE_CALLS:
                    continue
                scope = self._enclosing(mod, call)
                # engine entry points trace only their LOSS argument —
                # epoch_callback etc. are host-side hooks
                if d in ENGINE_CALLS:
                    candidates = list(call.args) + [
                        k.value for k in call.keywords
                        if k.arg and "loss" in k.arg]
                else:
                    candidates = list(call.args) + [k.value for k in
                                                    call.keywords]
                for arg in candidates:
                    target = None
                    if isinstance(arg, ast.Lambda):
                        target = mod.funcs.get(arg)
                    elif isinstance(arg, (ast.Name, ast.Attribute)):
                        target = self.resolve_ref(mod, arg, scope)
                    if target is not None:
                        if d == "jax.jit":
                            self._jit_meta_from_call(mod, call, target)
                        self._mark(target, f"passed to {d}")

    def _enclosing(self, mod: ModuleIndex, node) -> Optional[FuncInfo]:
        """Innermost FuncInfo whose body contains ``node`` (by position)."""
        best, best_span = None, None
        for fnode, fi in mod.funcs.items():
            if not hasattr(fnode, "lineno") or not hasattr(node, "lineno"):
                continue
            end = getattr(fnode, "end_lineno", fnode.lineno)
            if fnode.lineno <= node.lineno <= end:
                span = end - fnode.lineno
                if best_span is None or span < best_span:
                    best, best_span = fi, span
        return best

    def _mark(self, fi: FuncInfo, reason: str) -> None:
        if not fi.traced:
            fi.traced = True
            fi.traced_reason = reason
            self._worklist.append(fi)

    # -- propagation --------------------------------------------------------

    def _propagate(self) -> None:
        while self._worklist:
            fi = self._worklist.pop()
            # lexically nested defs run traced
            for child in fi.children.values():
                self._mark(child, f"nested in traced {fi.qualname}")
            # any function referenced inside the traced body is traced
            for node in ast.walk(fi.node):
                if isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    target = self.resolve_ref(fi.module, node, fi)
                    if target is not None and target is not fi:
                        self._mark(target,
                                   f"referenced by traced {fi.qualname}")


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

def _modpath_for(relpath: str) -> str:
    """src/repro/core/training.py -> repro.core.training"""
    p = relpath.replace(os.sep, "/")
    for prefix in ("src/",):
        if p.startswith(prefix):
            p = p[len(prefix):]
    p = p[:-3] if p.endswith(".py") else p
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def _collect_files(paths: Sequence[str], root: str) -> List[str]:
    files = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isdir(ap):
            for dirpath, _, names in os.walk(ap):
                files.extend(os.path.join(dirpath, n)
                             for n in names if n.endswith(".py"))
        elif ap.endswith(".py"):
            files.append(ap)
    return sorted(set(files))


def build_index(paths: Sequence[str], root: str) -> ProjectIndex:
    modules: Dict[str, ModuleIndex] = {}
    for ap in _collect_files(paths, root):
        rel = os.path.relpath(ap, root)
        with open(ap) as fh:
            source = fh.read()
        try:
            mod = ModuleIndex(ap, rel, _modpath_for(rel), source)
        except SyntaxError:
            continue                      # not this linter's job
        modules[mod.modpath] = mod
    return ProjectIndex(modules)


def run_rules(project: ProjectIndex,
              report_files: Optional[set] = None) -> List[Finding]:
    """Run every registered rule; ``report_files`` (repo-relative paths)
    restricts REPORTING, not indexing — cross-module tracedness always
    sees the full project (this is what makes ``--diff`` sound)."""
    from repro.analysis import rules as rules_pkg
    findings: List[Finding] = []
    for rule in rules_pkg.ALL_RULES:
        findings.extend(rule.check(project))
    if report_files is not None:
        findings = [f for f in findings if f.file in report_files]
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings


def lint_paths(paths: Sequence[str], *, root: str,
               baseline_path: Optional[str] = None,
               report_files: Optional[set] = None) -> List[Finding]:
    """Index ``paths`` under ``root`` and return non-baselined findings."""
    project = build_index(paths, root)
    findings = run_rules(project, report_files)
    if baseline_path:
        findings = apply_baseline(findings, load_baseline(baseline_path))
    return findings


def lint_source(source: str, *, modpath: str = "fixture",
                filename: str = "fixture.py") -> List[Finding]:
    """Lint a source snippet in isolation (the test-fixture entry point)."""
    mod = ModuleIndex(filename, filename, modpath, source)
    project = ProjectIndex({modpath: mod})
    return run_rules(project)
