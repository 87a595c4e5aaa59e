#!/usr/bin/env python
"""Cache-coherence static lint: AST checks for the two contracts every
compiled/persisted cache in this repo leans on (docs/DESIGN.md "Cache
discipline").  PRs 11-12 made per-shape autotune winners, the module
program caches (_SHARDED_PROGRAMS, _RING_PIPELINES), and the persisted
AOT executable cache the backbone of both perf and restart survival —
and a single value baked into a compiled program but missing from its
cache key silently serves STALE VERDICTS after a delta or a restart,
the wrong-answer failure mode that is strictly worse than a crash.
This pass makes key completeness and never-raise degradation
lint-enforced, the way locks (tools/locklint.py) and tensor shapes
(tools/shapelint.py) already are.

  CC001  trace-baked value not covered by the declared cache key: at an
         `AotProgram(...)` construction, a fill of a module-level
         program-cache dict (`_X_PROGRAMS[key] = fn`), or a
         module-global jit assignment, every closure-captured value and
         `self._*` attribute read baked into the wrapped body must be
         covered by the key — the key/`plan=`/`schedule=` expressions,
         a trailing `# cache-key: a, b, ...` comment, or a
         `cachekeys.program("a", "b")` descriptor
         (cyclonus_tpu/utils/cachekeys.py, the runtime twin).  One
         level of jaxlint-style inference applies both ways: a baked
         name ASSIGNED FROM covered values is covered (n_dev =
         mesh.devices.size), and a value the key DERIVES FROM is
         covered (leaves, treedef = tree_flatten(in_specs) covers
         in_specs when treedef/leaves are in the key).  A module
         program-cache dict with no `# cache-key:` declaration on its
         definition line flags.

  CC002  value-derived cache not registered for invalidation: in a
         class that defines `invalidate_after_patch`, an attribute
         declared `# derived-from: <tokens>` (trailing comment on its
         initializing assignment) with a VALUE token must be reset by
         `invalidate_after_patch`; the special tokens `shapes`
         (program/shape-derived — survives an in-place value patch)
         and `patched` (maintained in place by the patch path itself)
         are exempt.  A cache-patterned attribute (`*cache*`, `*_jit`,
         `*_aot`, `*_buf`, `*_dev`, `*device_tensors`, `*_programs`,
         `*_plan_state`) initialized in `__init__` WITHOUT any
         declaration flags — a new cache cannot silently skip the
         invalidation audit.

  CC003  env/config read on a cached path: os.environ / os.getenv
         reachable from a jit-traced or AotProgram-wrapped body (one
         level of same-module call-site inference) — the value is
         baked at trace time and a later env change silently serves
         the stale program.  The repo pattern is eager resolution
         (CYCLONUS_PACK -> engine._pack at construction).

  CC004  persisted-cache write discipline: in a module that defines
         CACHE_VERSION, a writer (a function calling os.replace) must
         stage through tempfile.mkstemp (atomic tmp + replace), must
         reference CACHE_VERSION and its cache `key` in the entry it
         writes, and a direct `open(path, "w"/"wb")` outside the
         tmp+replace idiom flags; a module with a persisted writer but
         no `# never-raises`-annotated load/read twin flags (a cache
         you can write but not safely read back is a crash on the next
         restart).

  CC005  never-raise contract: a function whose `def` line carries
         `# never-raises` is verified statement by statement — every
         risky statement (a call outside the safe set, a plain-index
         subscript, a raise) must sit under a `try` with a BROAD
         handler (bare / Exception / BaseException), or call only
         other `# never-raises` functions / whitelisted stdlib
         accessors; a broad handler that swallows without incrementing
         a counter (.inc / *count*) or logging flags — degradation
         must leave evidence.

Suppress a finding with `# cachelint: ignore` or
`# cachelint: ignore[CC001,...]` on the offending line (same convention
as tools/jaxlint.py / locklint.py / shapelint.py).

Usage: python tools/cachelint.py [paths...]
       (default: cyclonus_tpu/engine cyclonus_tpu/serve
        cyclonus_tpu/chaos)
Exit status 1 iff findings remain.
"""

from __future__ import annotations

import ast
import builtins
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from lintcore import Finding, ignore_regex, iter_py_files, run_cli
from lintcore import suppress as _core_suppress

_IGNORE_RE = ignore_regex("cachelint")
_CACHE_KEY_RE = re.compile(r"#\s*cache-key:\s*(.+)")
_DERIVED_RE = re.compile(r"#\s*derived-from:\s*(.+)")
_NEVER_RAISES_RE = re.compile(r"#\s*never-raises")
_TOKEN_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")

#: derived-from tokens that do NOT demand an invalidate_after_patch
#: reset: `shapes` = program/shape-derived (an in-place value patch
#: keeps it valid), `patched` = the patch path maintains it in place
DERIVED_EXEMPT_TOKENS = {"shapes", "patched"}

#: attribute-name pattern that marks a per-engine cache (CC002's "new
#: cache attribute" heuristic)
_CACHE_ATTR_RE = re.compile(
    r"cache|_jit$|_aot$|_buf$|_dev$|device_tensors$|_programs$"
    r"|_pipelines$|_plan_state$"
)

#: callables whose construction arguments become part of a compiled
#: program (their argument names are trace-baked surface for CC001)
_PROGRAM_CTOR_NAMES = {"jit", "pjit", "shard_map", "shard_map_no_check"}

# -- CC005 whitelists -------------------------------------------------------

#: dotted-call prefixes that cannot realistically raise in these
#: degradation paths (attribute chains joined with '.')
SAFE_CALL_PREFIXES = (
    "os.path.",
    "os.environ.get",
    "os.getpid",
    "time.",
    "math.",
    "hashlib.",
    "logging.getLogger",
    # the central env-flag registry accessors are never-raise by
    # construction (unparseable values degrade to the registered
    # default; tests/test_envflags.py pins it) — both import spellings
    "envflags.get_",
    "utils.envflags.get_",
)
#: bare builtins safe to call with any argument
SAFE_BARE_CALLS = {
    "len", "isinstance", "issubclass", "getattr", "hasattr", "repr",
    "str", "dict", "list", "tuple", "set", "sorted", "min", "max",
    "type", "callable", "id", "bool", "print", "format", "zip",
    "enumerate", "range",
}
#: method names safe on any receiver (string/dict/metric accessors the
#: degradation paths use; .inc/.set/.observe are this repo's own
#: metric ops, which are never-raise by construction)
SAFE_METHOD_ATTRS = {
    "get", "strip", "lower", "upper", "split", "rsplit", "join",
    "startswith", "endswith", "items", "keys", "values", "encode",
    "decode", "hexdigest", "append", "setdefault", "copy", "format",
    "expanduser", "inc", "set", "observe", "warning", "info", "error",
    "exception", "debug", "bit_length",
}
#: handler-body calls that count as swallow EVIDENCE (counter or log)
EVIDENCE_ATTRS = {
    "inc", "observe", "warning", "info", "error", "exception", "debug",
}


def _attr_root(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_chain(node: ast.AST) -> Optional[str]:
    """'os.environ.get' for a nested Attribute, None when not rooted at
    a Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _self_attr(node: ast.AST) -> Optional[str]:
    """'self.x' for Attribute(value=Name('self'))."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return f"self.{node.attr}"
    return None


def _components(text: str) -> List[str]:
    """Parse a `# cache-key:` / `# derived-from:` component list: split
    on commas, keep each item's leading identifier token (a trailing
    parenthetical note is welcome but cannot contain commas)."""
    out = []
    for part in text.split(","):
        m = _TOKEN_RE.search(part)
        if m:
            out.append(m.group(0))
    return out


def _trailing(lines: List[str], lineno: int, regex: re.Pattern) -> Optional[str]:
    if 0 < lineno <= len(lines):
        m = regex.search(lines[lineno - 1])
        if m:
            return m.group(1) if m.groups() else m.group(0)
    return None


def _names_and_self_attrs(expr: ast.AST) -> Set[str]:
    """Every Name load and 'self.x' chain referenced in an expression,
    excluding names the expression binds itself (comprehension targets,
    lambda parameters) — those are expression-local, not references to
    the enclosing scope."""
    bound: Set[str] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.comprehension):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
        elif isinstance(node, ast.Lambda):
            a = node.args
            bound |= {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
        elif isinstance(node, ast.NamedExpr) and isinstance(
            node.target, ast.Name
        ):
            bound.add(node.target.id)
    out: Set[str] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id not in bound:
            out.add(node.id)
        sa = _self_attr(node)
        if sa:
            out.add(sa)
    out.discard("self")
    return out


def _bound_names(fn: ast.AST) -> Set[str]:
    """Names bound inside a function: params, assignments, imports,
    nested defs, comprehension/loop/with targets."""
    a = fn.args
    bound = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        bound.add(sub.id)
        elif isinstance(node, (ast.For, ast.comprehension)):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
        elif isinstance(node, ast.With):
            for item in node.items:
                if item.optional_vars is not None:
                    for sub in ast.walk(item.optional_vars):
                        if isinstance(sub, ast.Name):
                            bound.add(sub.id)
        elif isinstance(node, ast.Import):
            for al in node.names:
                bound.add(al.asname or al.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            for al in node.names:
                bound.add(al.asname or al.name)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound.add(node.name)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
        elif isinstance(node, ast.Lambda):
            la = node.args
            bound |= {x.arg for x in la.posonlyargs + la.args + la.kwonlyargs}
    return bound


def _free_loads(fn: ast.AST) -> Set[str]:
    """Free variables of a def/lambda: Name loads not bound within, plus
    'self.x' attribute reads (the closure-captured surface CC001
    audits).  `self` alone is not free — only its attributes are."""
    bound = _bound_names(fn)
    out: Set[str] = set()
    body = fn.body if isinstance(fn.body, list) else [ast.Expr(fn.body)]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                if node.id not in bound:
                    out.add(node.id)
            sa = _self_attr(node)
            if sa:
                out.add(sa)
    out.discard("self")
    return out


class ModuleModel:
    """Per-module facts shared by every check."""

    def __init__(self, path: str, tree: ast.Module, lines: List[str]):
        self.path = path
        self.tree = tree
        self.lines = lines
        self.aliases: Dict[str, str] = {}
        self.module_names: Set[str] = set()
        self.functions: Dict[str, ast.FunctionDef] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        #: module-level dict caches: name -> (declared components or
        #: None, definition line)
        self.cache_dicts: Dict[str, Tuple[Optional[List[str]], int]] = {}
        #: module-level plain globals (for the module-global jit check)
        self.global_lines: Dict[str, int] = {}
        self.has_cache_version = False
        self.never_raise_funcs: Set[str] = set()
        self.never_raise_methods: Dict[str, Set[str]] = {}
        # annotation census (the acceptance gate counts live lines)
        self.n_cache_keys = sum(
            1 for ln in lines if _CACHE_KEY_RE.search(ln)
        )
        self.n_derived = sum(1 for ln in lines if _DERIVED_RE.search(ln))
        self.n_never_raises = sum(
            1 for ln in lines if _NEVER_RAISES_RE.search(ln)
        )

        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for al in node.names:
                    self.aliases[al.asname or al.name.split(".")[0]] = al.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for al in node.names:
                    self.aliases[al.asname or al.name] = (
                        f"{node.module}.{al.name}"
                    )

        for stmt in tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[stmt.name] = stmt
                self.module_names.add(stmt.name)
            elif isinstance(stmt, ast.ClassDef):
                self.classes[stmt.name] = stmt
                self.module_names.add(stmt.name)
            elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
                for al in stmt.names:
                    self.module_names.add(
                        al.asname or al.name.split(".")[0]
                    )
            elif isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                targets = (
                    stmt.targets
                    if isinstance(stmt, ast.Assign)
                    else [stmt.target]
                )
                for t in targets:
                    if not isinstance(t, ast.Name):
                        continue
                    self.module_names.add(t.id)
                    self.global_lines[t.id] = stmt.lineno
                    if t.id == "CACHE_VERSION":
                        self.has_cache_version = True
                    if isinstance(stmt.value, (ast.Dict, ast.DictComp)) or (
                        isinstance(stmt.value, ast.Call)
                        and isinstance(stmt.value.func, ast.Name)
                        and stmt.value.func.id == "dict"
                    ):
                        decl = _trailing(lines, stmt.lineno, _CACHE_KEY_RE)
                        comps = _components(decl) if decl else None
                        self.cache_dicts[t.id] = (comps, stmt.lineno)

        # never-raises annotations on def lines (functions and methods)
        def scan_defs(body, owner: Optional[str]) -> None:
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if _trailing(lines, node.lineno, _NEVER_RAISES_RE):
                        if owner is None:
                            self.never_raise_funcs.add(node.name)
                        else:
                            self.never_raise_methods.setdefault(
                                owner, set()
                            ).add(node.name)
                elif isinstance(node, ast.ClassDef):
                    scan_defs(node.body, node.name)

        scan_defs(tree.body, None)

    def is_exempt_name(self, name: str, local_imports: Set[str]) -> bool:
        """A name that cannot be a trace-baked VALUE: module-level
        bindings (functions, classes, imports, ALL_CAPS constants are
        module-owned, the jaxlint JX004 domain), builtins, and
        function-level imports."""
        if name in local_imports:
            return True
        if name in self.module_names or name in self.aliases:
            return True
        if hasattr(builtins, name):
            return True
        return name.isupper() or (name.startswith("_") and name[1:].isupper())


# -- CC001 -----------------------------------------------------------------


class FunctionSites:
    """CC001 over one function (or the module body pseudo-function):
    find AotProgram / cache-dict-fill / module-global-jit sites, compute
    the baked and covered sets, emit findings."""

    def __init__(
        self,
        model: ModuleModel,
        cls: Optional[ast.ClassDef],
        func: ast.AST,
        body: List[ast.stmt],
    ):
        self.model = model
        self.cls = cls
        self.func = func
        self.body = body
        self.findings: List[Finding] = []
        # local structure
        self.assigns: Dict[str, List[ast.expr]] = {}
        self.local_defs: Dict[str, ast.AST] = {}
        self.local_imports: Set[str] = set()
        self.params: Set[str] = set()
        if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = func.args
            self.params = {
                x.arg for x in a.posonlyargs + a.args + a.kwonlyargs
            }
        def bind(t: ast.AST, value: ast.expr) -> None:
            # only NAME bindings map to the value; a subscript/attribute
            # store does not bind its index/receiver names (treating
            # `CACHE[key] = fn` as an assignment of `key` would leak
            # the program's refs into the covered set backwards)
            if isinstance(t, ast.Name):
                self.assigns.setdefault(t.id, []).append(value)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    bind(el, value)
            elif isinstance(t, ast.Starred):
                bind(t.value, value)

        for node in [n for s in body for n in ast.walk(s)]:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    bind(t, node.value)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                bind(node.target, node.value)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if node is not func:
                    self.local_defs[node.name] = node
            elif isinstance(node, ast.Import):
                for al in node.names:
                    self.local_imports.add(al.asname or al.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                for al in node.names:
                    self.local_imports.add(al.asname or al.name)

    # -- baked-set collection ------------------------------------------

    def _classify(self, name: str, out: Set[str], seen: Set[int]) -> None:
        if name.startswith("self."):
            out.add(name)
            return
        if name in self.local_defs:
            self._add_def_frees(self.local_defs[name], out, seen)
            return
        if self.model.is_exempt_name(name, self.local_imports):
            return
        if name in self.params or name in self.assigns:
            out.add(name)

    def _classify_expr(self, expr: ast.AST, out: Set[str], seen: Set[int]) -> None:
        for n in _names_and_self_attrs(expr):
            self._classify(n, out, seen)

    def _add_def_frees(self, fn: ast.AST, out: Set[str], seen: Set) -> None:
        # namespaced guard: `visit` tracks raw node ids in the same set
        if ("def", id(fn)) in seen:
            return
        seen.add(("def", id(fn)))
        for name in _free_loads(fn):
            self._classify(name, out, seen)
        # default expressions evaluate in the enclosing scope at def
        # time: `def body(t, _n=n_dev)` bakes n_dev
        args = getattr(fn, "args", None)
        if args is not None:
            for d in list(args.defaults) + [d for d in args.kw_defaults if d]:
                self._classify_expr(d, out, seen)

    def _is_program_ctor(self, call: ast.Call) -> bool:
        f = call.func
        name = (
            f.attr
            if isinstance(f, ast.Attribute)
            else f.id if isinstance(f, ast.Name) else None
        )
        return name in _PROGRAM_CTOR_NAMES

    def _is_aot_ctor(self, call: ast.Call) -> bool:
        f = call.func
        name = (
            f.attr
            if isinstance(f, ast.Attribute)
            else f.id if isinstance(f, ast.Name) else None
        )
        return name == "AotProgram"

    def collect_baked(self, expr: ast.AST, seen: Optional[Set[int]] = None) -> Set[str]:
        """The trace-baked surface of a program-constructing expression:
        free variables (and argument-expression names) of every lambda,
        local def, jit/shard_map call, and AotProgram call reachable
        from `expr`.  Plain calls (dict lookups etc.) are ignored — they
        run at fill time, not inside the compiled body."""
        out: Set[str] = set()
        seen = set() if seen is None else seen

        def visit(e: ast.AST) -> None:
            if e is None or id(e) in seen:
                return
            seen.add(id(e))
            if isinstance(e, ast.Lambda):
                self._add_def_frees(e, out, seen)
                return
            if isinstance(e, ast.Name):
                if e.id in self.local_defs:
                    self._add_def_frees(self.local_defs[e.id], out, seen)
                elif e.id in self.assigns:
                    for rhs in self.assigns[e.id]:
                        visit(rhs)
                return
            sa = _self_attr(e)
            if sa is not None and not isinstance(e.ctx, ast.Store):
                # a bound method / closure stored on self, wrapped whole
                out.add(sa)
                return
            if isinstance(e, ast.Call):
                if self._is_program_ctor(e):
                    if e.args:
                        visit(e.args[0])
                    for a in e.args[1:]:
                        self._classify_expr(a, out, seen)
                    for kw in e.keywords:
                        self._classify_expr(kw.value, out, seen)
                    return
                if self._is_aot_ctor(e):
                    if len(e.args) > 1:
                        visit(e.args[1])
                    for kw in e.keywords:
                        self._classify_expr(kw.value, out, seen)
                    return
                # plain call: not program construction — ignore
                return
            if isinstance(e, (ast.Tuple, ast.List)):
                for el in e.elts:
                    visit(el)
                return
            for child in ast.iter_child_nodes(e):
                visit(child)

        visit(expr)
        return out

    # -- covered-set construction --------------------------------------

    def _expand_method(self, call: ast.Call, covered: Set[str]) -> None:
        """plan=self._aot_plan(...) — one level into the same-class
        method: the self attributes its body reads are covered key
        components, and so are the call's own argument names."""
        f = call.func
        if not (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id == "self"
            and self.cls is not None
        ):
            return
        for sub in self.cls.body:
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub.name == f.attr
            ):
                for node in ast.walk(sub):
                    sa = _self_attr(node)
                    if sa:
                        covered.add(sa)
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            covered.update(_names_and_self_attrs(a))

    def _method_self_attrs(self, ref: str) -> Set[str]:
        """'self.M' -> the self attributes method M of the enclosing
        class reads (empty for non-methods)."""
        if not ref.startswith("self.") or self.cls is None:
            return set()
        meth = ref[5:]
        out: Set[str] = set()
        for sub in self.cls.body:
            if (
                isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                and sub.name == meth
            ):
                for node in ast.walk(sub):
                    sa = _self_attr(node)
                    if sa:
                        out.add(sa)
        return out

    def _comment_components(self, lo: int, hi: int) -> Set[str]:
        out: Set[str] = set()
        for ln in range(lo, hi + 1):
            decl = _trailing(self.model.lines, ln, _CACHE_KEY_RE)
            if decl:
                out.update(_components(decl))
        return out

    def _descriptor_components(self) -> Set[str]:
        """cachekeys.program("a", "b") descriptor calls anywhere in the
        function declare covered components."""
        out: Set[str] = set()
        for stmt in self.body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                chain = _attr_chain(node.func)
                if chain is None or not chain.endswith("cachekeys.program"):
                    if not (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr == "program"
                        and _attr_root(node.func) == "cachekeys"
                    ):
                        continue
                for a in node.args:
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        out.add(a.value)
                for kw in node.keywords:
                    for sub in ast.walk(kw.value):
                        if isinstance(sub, ast.Constant) and isinstance(
                            sub.value, str
                        ):
                            out.add(sub.value)
        return out

    def _close_over_derivations(
        self, baked: Set[str], covered: Set[str]
    ) -> Set[str]:
        """One-level-each-way derivation closure (module docstring):
        forward — a baked local assigned only from covered/exempt names
        is covered; backward — names a covered local's assignment
        references are covered (the key embeds a digest of them)."""
        covered = set(covered)
        for _ in range(6):
            before = len(covered)
            # backward
            for c in list(covered):
                for rhs in self.assigns.get(c, []):
                    for r in _names_and_self_attrs(rhs):
                        if not self.model.is_exempt_name(
                            r, self.local_imports
                        ) or r.startswith("self."):
                            covered.add(r)
                            # self.M where M is a same-class method:
                            # the key derives from its return value, so
                            # the self attributes ITS body reads are
                            # key components too (one level)
                            covered |= self._method_self_attrs(r)
            # forward
            for b in list(baked - covered):
                if b.startswith("self."):
                    continue
                for rhs in self.assigns.get(b, []):
                    refs = {
                        r
                        for r in _names_and_self_attrs(rhs)
                        if r.startswith("self.")
                        or not self.model.is_exempt_name(
                            r, self.local_imports
                        )
                    }
                    if all(r in covered for r in refs):
                        covered.add(b)
                        break
                # a baked self attribute assigned in __init__ cannot be
                # chased here; it must be covered explicitly
            if len(covered) == before:
                break
        return covered

    # -- site checks ----------------------------------------------------

    def _scope_walk(self, stmts: List[ast.stmt]):
        """Walk statements WITHOUT descending into nested function
        defs: each site belongs to exactly one (innermost) scope, whose
        assignment map is the one that resolves its names."""
        stack = list(stmts)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested scope: its own FunctionSites pass owns it
            yield node
            for child in ast.iter_child_nodes(node):
                stack.append(child)

    def check(self) -> None:
        for node in self._scope_walk(self.body):
            if isinstance(node, ast.Call) and self._is_aot_ctor(node):
                self._check_aot_site(node)
            elif isinstance(node, ast.Assign):
                self._check_fill_site(node)

    def _check_aot_site(self, call: ast.Call) -> None:
        name = (
            call.args[0].value
            if call.args
            and isinstance(call.args[0], ast.Constant)
            and isinstance(call.args[0].value, str)
            else "<?>"
        )
        baked: Set[str] = set()
        if len(call.args) > 1:
            baked = self.collect_baked(call.args[1])
        covered: Set[str] = set()
        for kw in call.keywords:
            if kw.arg in ("plan", "schedule"):
                covered.update(_names_and_self_attrs(kw.value))
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Call):
                        self._expand_method(sub, covered)
            if kw.arg == "static_argnames":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) and isinstance(
                        sub.value, str
                    ):
                        covered.add(sub.value)
        covered |= self._comment_components(
            call.lineno, call.end_lineno or call.lineno
        )
        covered |= self._descriptor_components()
        covered = self._close_over_derivations(baked, covered)
        for miss in sorted(baked - covered):
            self._add(
                call,
                "CC001",
                f"trace-baked value '{miss}' is not covered by the cache "
                f"key of AotProgram '{name}' (a stale program outlives a "
                f"change to it; put it in plan=/schedule=, list it in a "
                f"trailing `# cache-key:` comment, or pass it as an "
                f"argument)",
            )

    def _check_fill_site(self, stmt: ast.Assign) -> None:
        """`_PROGRAMS[key] = value` fills of module cache dicts, plus
        module-global jit rebinds (`global _JIT; _JIT = jax.jit(...)`)."""
        for t in stmt.targets:
            if (
                isinstance(t, ast.Subscript)
                and isinstance(t.value, ast.Name)
                and t.value.id in self.model.cache_dicts
            ):
                self._check_dict_fill(t.value.id, t.slice, stmt)
            elif (
                isinstance(t, ast.Name)
                and t.id in self.model.global_lines
                and any(
                    isinstance(n, ast.Call) and self._is_program_ctor(n)
                    for n in ast.walk(stmt.value)
                )
            ):
                self._check_global_jit(t.id, stmt)

    def _check_dict_fill(
        self, dict_name: str, key_expr: ast.AST, stmt: ast.Assign
    ) -> None:
        baked = self.collect_baked(stmt.value)
        stores_program = baked or any(
            (isinstance(n, ast.Call) and (self._is_program_ctor(n) or self._is_aot_ctor(n)))
            or isinstance(n, ast.Lambda)
            or (isinstance(n, ast.Name) and n.id in self.local_defs)
            for n in ast.walk(stmt.value)
        )
        comps, decl_line = self.model.cache_dicts[dict_name]
        if comps is None:
            if stores_program:
                self._add(
                    stmt,
                    "CC001",
                    f"module program cache '{dict_name}' (line {decl_line}) "
                    f"has no `# cache-key:` declaration on its definition "
                    f"line",
                )
            if not baked:
                return
            comps = []
        covered: Set[str] = set(comps)
        covered.update(_names_and_self_attrs(key_expr))
        if isinstance(key_expr, ast.Name):
            covered.add(key_expr.id)
        covered |= self._comment_components(
            stmt.lineno, stmt.end_lineno or stmt.lineno
        )
        covered |= self._descriptor_components()
        covered = self._close_over_derivations(baked, covered)
        for miss in sorted(baked - covered):
            self._add(
                stmt,
                "CC001",
                f"trace-baked value '{miss}' is not covered by the key "
                f"stored into module program cache '{dict_name}' (a "
                f"same-key lookup would serve a program compiled for a "
                f"different '{miss}')",
            )

    def _check_global_jit(self, gname: str, stmt: ast.Assign) -> None:
        baked = self.collect_baked(stmt.value)
        covered = self._comment_components(
            stmt.lineno, stmt.end_lineno or stmt.lineno
        )
        decl_line = self.model.global_lines.get(gname)
        if decl_line:
            covered |= {
                c
                for c in _components(
                    _trailing(self.model.lines, decl_line, _CACHE_KEY_RE) or ""
                )
            }
        covered |= self._descriptor_components()
        covered = self._close_over_derivations(baked, covered)
        for miss in sorted(baked - covered):
            self._add(
                stmt,
                "CC001",
                f"module-global program '{gname}' bakes '{miss}' with no "
                f"cache key at all (process-lifetime staleness; declare "
                f"`# cache-key:` or key the program per value)",
            )

    def _add(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(
            Finding(
                self.model.path, node.lineno, node.col_offset, code, message
            )
        )


# -- CC002 -----------------------------------------------------------------


def derived_model(
    model: ModuleModel, cls: ast.ClassDef
) -> Tuple[Dict[str, Tuple[List[str], int]], Optional[ast.AST], Set[str]]:
    """(declarations, invalidate_after_patch node, attrs it resets) for
    one class.  Declarations map attr -> (tokens, line) from
    `# derived-from:` trailing comments on `self.X = ...` lines in any
    method."""
    decls: Dict[str, Tuple[List[str], int]] = {}
    invalidate: Optional[ast.AST] = None
    for sub in cls.body:
        if not isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if sub.name == "invalidate_after_patch":
            invalidate = sub
        for node in ast.walk(sub):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                sa = _self_attr(t)
                if sa is None:
                    continue
                decl = _trailing(model.lines, node.lineno, _DERIVED_RE)
                if decl:
                    attr = sa[5:]
                    if attr not in decls:
                        decls[attr] = (_components(decl), node.lineno)
    reset: Set[str] = set()
    if invalidate is not None:
        for node in ast.walk(invalidate):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for t in targets:
                    sa = _self_attr(t)
                    if sa:
                        reset.add(sa[5:])
    return decls, invalidate, reset


def check_cc002(model: ModuleModel) -> List[Finding]:
    findings: List[Finding] = []
    for cls in model.classes.values():
        decls, invalidate, reset = derived_model(model, cls)
        if invalidate is None:
            continue
        for attr, (tokens, line) in decls.items():
            value_tokens = [
                t for t in tokens if t not in DERIVED_EXEMPT_TOKENS
            ]
            if value_tokens and attr not in reset:
                findings.append(
                    Finding(
                        model.path,
                        line,
                        0,
                        "CC002",
                        f"{cls.name}.{attr} is declared value-derived "
                        f"(`# derived-from: {', '.join(tokens)}`) but "
                        f"invalidate_after_patch never resets it — a "
                        f"patched buffer would serve its stale contents",
                    )
                )
        # new cache attributes must declare themselves
        init = next(
            (
                s
                for s in cls.body
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
                and s.name == "__init__"
            ),
            None,
        )
        if init is None:
            continue
        for node in ast.walk(init):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                sa = _self_attr(t)
                if sa is None:
                    continue
                attr = sa[5:]
                if _CACHE_ATTR_RE.search(attr) and attr not in decls:
                    findings.append(
                        Finding(
                            model.path,
                            node.lineno,
                            node.col_offset,
                            "CC002",
                            f"cache attribute {cls.name}.{attr} has no "
                            f"`# derived-from:` declaration (new caches "
                            f"must name what they derive from so the "
                            f"invalidation audit sees them; use 'shapes' "
                            f"for program caches, 'patched' for state "
                            f"the patch path maintains in place)",
                        )
                    )
    return findings


# -- CC003 -----------------------------------------------------------------


def _is_env_read(node: ast.Call, aliases: Dict[str, str]) -> Optional[str]:
    chain = _attr_chain(node.func)
    if chain is None:
        return None
    root = chain.split(".")[0]
    resolved = aliases.get(root, root)
    full = ".".join([resolved] + chain.split(".")[1:])
    if full in ("os.environ.get", "os.getenv"):
        return full
    if full.startswith("os.environ"):
        return full
    return None


def _env_subscript(node: ast.Subscript, aliases: Dict[str, str]) -> bool:
    chain = _attr_chain(node.value)
    if chain is None:
        return False
    root = chain.split(".")[0]
    resolved = aliases.get(root, root)
    full = ".".join([resolved] + chain.split(".")[1:])
    return full == "os.environ"


def collect_traced_functions(model: ModuleModel) -> List[ast.AST]:
    """Functions whose bodies trace into a compiled program: jit
    decorated/wrapped defs and lambdas, AotProgram-wrapped local defs,
    shard_map bodies."""
    out: List[ast.AST] = []
    seen: Set[int] = set()
    all_defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(model.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            all_defs.setdefault(node.name, []).append(node)

    def add(fn: ast.AST) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append(fn)

    def is_jit_expr(e: ast.AST) -> bool:
        if isinstance(e, ast.Attribute) and e.attr in _PROGRAM_CTOR_NAMES:
            return True
        if isinstance(e, ast.Name):
            if e.id in _PROGRAM_CTOR_NAMES:
                return True
            return model.aliases.get(e.id, "").endswith(".jit")
        return False

    for node in ast.walk(model.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if is_jit_expr(dec):
                    add(node)
                elif isinstance(dec, ast.Call) and is_jit_expr(dec.func):
                    add(node)
        elif isinstance(node, ast.Call):
            target = None
            if is_jit_expr(node.func) and node.args:
                target = node.args[0]
            elif (
                isinstance(node.func, (ast.Attribute, ast.Name))
                and (
                    getattr(node.func, "attr", None) == "AotProgram"
                    or getattr(node.func, "id", None) == "AotProgram"
                )
                and len(node.args) > 1
            ):
                target = node.args[1]
            if target is None:
                continue
            if isinstance(target, ast.Lambda):
                add(target)
            elif isinstance(target, ast.Name):
                for fn in all_defs.get(target.id, []):
                    add(fn)
    return out


def check_cc003(model: ModuleModel) -> List[Finding]:
    findings: List[Finding] = []
    traced = collect_traced_functions(model)
    traced_ids = {id(f) for f in traced}

    def env_findings(fn: ast.AST, via: str) -> None:
        body = fn.body if isinstance(fn.body, list) else [ast.Expr(fn.body)]
        for stmt in body:
            for node in ast.walk(stmt):
                hit = None
                if isinstance(node, ast.Call):
                    hit = _is_env_read(node, model.aliases)
                elif isinstance(node, ast.Subscript):
                    if _env_subscript(node, model.aliases):
                        hit = "os.environ[...]"
                if hit:
                    findings.append(
                        Finding(
                            model.path,
                            node.lineno,
                            node.col_offset,
                            "CC003",
                            f"{hit} read on a cached/compiled path{via} — "
                            f"the value bakes in at trace time and a "
                            f"later env change serves the stale program; "
                            f"resolve it eagerly (the CYCLONUS_PACK "
                            f"pattern) and key the program on it",
                        )
                    )

    for fn in traced:
        env_findings(fn, "")
        # one level of same-module call-site inference
        name = getattr(fn, "name", "<lambda>")
        body = fn.body if isinstance(fn.body, list) else [ast.Expr(fn.body)]
        for stmt in body:
            for node in ast.walk(stmt):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in model.functions
                ):
                    callee = model.functions[node.func.id]
                    if id(callee) not in traced_ids:
                        env_findings(
                            callee,
                            f" (helper '{node.func.id}' reached from "
                            f"jit-traced '{name}')",
                        )
    # dedupe (a helper reached from several jit bodies)
    uniq: Dict[Tuple, Finding] = {}
    for f in findings:
        uniq.setdefault((f.path, f.line, f.col, f.code), f)
    return list(uniq.values())


# -- CC004 -----------------------------------------------------------------


def check_cc004(model: ModuleModel) -> List[Finding]:
    if not model.has_cache_version:
        return []
    findings: List[Finding] = []
    writers: List[ast.AST] = []

    def all_funcs():
        for node in ast.walk(model.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    for fn in all_funcs():
        calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)]
        has_replace = any(
            _attr_chain(c.func) in ("os.replace", "os.rename") for c in calls
        )
        has_mkstemp = any(
            (_attr_chain(c.func) or "").endswith("mkstemp")
            or (_attr_chain(c.func) or "").endswith("NamedTemporaryFile")
            for c in calls
        )
        names = {
            n.id for n in ast.walk(fn) if isinstance(n, ast.Name)
        }
        if has_replace:
            writers.append(fn)
            if not has_mkstemp:
                findings.append(
                    Finding(
                        model.path, fn.lineno, fn.col_offset, "CC004",
                        f"persisted-cache writer '{fn.name}' replaces "
                        f"without a tempfile.mkstemp stage (a reader can "
                        f"observe a half-written entry)",
                    )
                )
            if "CACHE_VERSION" not in names:
                findings.append(
                    Finding(
                        model.path, fn.lineno, fn.col_offset, "CC004",
                        f"persisted-cache writer '{fn.name}' does not "
                        f"embed CACHE_VERSION in the entry (a layout "
                        f"change would load as garbage instead of "
                        f"invalidating)",
                    )
                )
            if not any("key" in n for n in names):
                findings.append(
                    Finding(
                        model.path, fn.lineno, fn.col_offset, "CC004",
                        f"persisted-cache writer '{fn.name}' does not "
                        f"embed its cache key in the entry (a digest "
                        f"collision or stale stamp would load silently)",
                    )
                )
        else:
            for c in calls:
                fname = (
                    c.func.id
                    if isinstance(c.func, ast.Name)
                    else getattr(c.func, "attr", None)
                )
                if fname != "open" or len(c.args) < 2:
                    continue
                mode = c.args[1]
                if isinstance(mode, ast.Constant) and isinstance(
                    mode.value, str
                ) and "w" in mode.value:
                    findings.append(
                        Finding(
                            model.path, c.lineno, c.col_offset, "CC004",
                            f"direct open(..., {mode.value!r}) in a "
                            f"CACHE_VERSION module outside the atomic "
                            f"tmp+os.replace idiom (torn cache entry on "
                            f"a crash mid-write)",
                        )
                    )
    if writers:
        read_twin = any(
            re.match(r"^_?(load|read)", name)
            for name in model.never_raise_funcs
        ) or any(
            re.match(r"^_?(load|read)", m)
            for ms in model.never_raise_methods.values()
            for m in ms
        )
        if not read_twin:
            fn = writers[0]
            findings.append(
                Finding(
                    model.path, fn.lineno, fn.col_offset, "CC004",
                    "persisted write path without a `# never-raises` "
                    "annotated load/read twin (corrupt entries must "
                    "degrade to a fresh build, never crash the restart)",
                )
            )
    return findings


# -- CC005 -----------------------------------------------------------------


class NeverRaiseChecker:
    """Statement-by-statement verification of one `# never-raises`
    function."""

    def __init__(
        self,
        model: ModuleModel,
        cls_name: Optional[str],
        fn: ast.AST,
    ):
        self.model = model
        self.cls_name = cls_name
        self.fn = fn
        self.findings: List[Finding] = []

    def _safe_call(self, call: ast.Call) -> bool:
        f = call.func
        if isinstance(f, ast.Name):
            if f.id in SAFE_BARE_CALLS:
                return True
            if f.id in self.model.never_raise_funcs:
                return True
            if "count" in f.id:
                return True
            return False
        chain = _attr_chain(f)
        if chain is not None:
            root = chain.split(".")[0]
            resolved = self.model.aliases.get(root, root)
            full = ".".join([resolved] + chain.split(".")[1:])
            for prefix in SAFE_CALL_PREFIXES:
                if full == prefix.rstrip(".") or full.startswith(prefix):
                    return True
            if chain.startswith("self.") and self.cls_name:
                meth = chain.split(".")[1]
                if meth in self.model.never_raise_methods.get(
                    self.cls_name, set()
                ):
                    return True
        if isinstance(f, ast.Attribute) and f.attr in SAFE_METHOD_ATTRS:
            return True
        return False

    def _risky(self, stmt: ast.AST) -> Optional[Tuple[ast.AST, str]]:
        # bounded walk: a nested def/lambda body runs at CALL time, not
        # here — its contents are not this statement's risk
        stack = [stmt]
        nodes = []
        while stack:
            n = stack.pop()
            if isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            nodes.append(n)
            stack.extend(ast.iter_child_nodes(n))
        for node in nodes:
            if isinstance(node, ast.Raise):
                return node, "raise statement"
            if isinstance(node, ast.Call) and not self._safe_call(node):
                name = _attr_chain(node.func) or getattr(
                    node.func, "id", "<call>"
                )
                return node, f"call to {name}()"
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and not isinstance(node.slice, ast.Slice)
            ):
                return node, "plain-index subscript"
        return None

    @staticmethod
    def _broad_handler(h: ast.ExceptHandler) -> bool:
        t = h.type
        if t is None:
            return True
        names = []
        if isinstance(t, ast.Tuple):
            names = [getattr(e, "id", None) for e in t.elts]
        else:
            names = [getattr(t, "id", None)]
        return any(n in ("Exception", "BaseException") for n in names)

    def _handler_has_evidence(self, h: ast.ExceptHandler) -> bool:
        for node in ast.walk(h):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and f.attr in EVIDENCE_ATTRS:
                    return True
                if isinstance(f, ast.Name) and "count" in f.id:
                    return True
        return False

    def run(self) -> List[Finding]:
        for stmt in self.fn.body:
            self._visit(stmt)
        return self.findings

    def _visit(self, stmt: ast.AST) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(stmt, ast.Try):
            shielded = any(self._broad_handler(h) for h in stmt.handlers)
            for h in stmt.handlers:
                if self._broad_handler(h) and not self._handler_has_evidence(h):
                    self.findings.append(
                        Finding(
                            self.model.path,
                            h.lineno,
                            h.col_offset,
                            "CC005",
                            f"never-raises '{self._name()}' swallows "
                            f"exceptions without evidence — the handler "
                            f"must increment a counter, log, or re-raise "
                            f"(silent degradation is undebuggable)",
                        )
                    )
            if not shielded:
                for s in stmt.body:
                    self._visit(s)
            for s in stmt.orelse + stmt.finalbody:
                self._visit(s)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            risk = self._risky_expr_only(stmt)
            if risk:
                self._flag(*risk)
            for s in stmt.body + stmt.orelse:
                self._visit(s)
            return
        if isinstance(stmt, ast.For):
            risk = self._risky_expr_only(stmt)
            if risk:
                self._flag(*risk)
            for s in stmt.body + stmt.orelse:
                self._visit(s)
            return
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                risk = self._risky(item.context_expr)
                if risk:
                    self._flag(*risk)
            for s in stmt.body:
                self._visit(s)
            return
        risk = self._risky(stmt)
        if risk:
            self._flag(*risk)

    def _risky_expr_only(self, stmt) -> Optional[Tuple[ast.AST, str]]:
        """Risk of a compound statement's OWN expressions (test/iter),
        not its body (visited separately)."""
        expr = stmt.test if isinstance(stmt, (ast.If, ast.While)) else stmt.iter
        return self._risky(expr)

    def _name(self) -> str:
        return getattr(self.fn, "name", "<lambda>")

    def _flag(self, node: ast.AST, what: str) -> None:
        self.findings.append(
            Finding(
                self.model.path,
                node.lineno,
                node.col_offset,
                "CC005",
                f"never-raises '{self._name()}' has an unshielded "
                f"{what} — wrap it in a try with a broad handler or "
                f"call only `# never-raises` / whitelisted functions",
            )
        )


def check_cc005(model: ModuleModel) -> List[Finding]:
    findings: List[Finding] = []

    def scan(body, owner: Optional[str]) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                annotated = (
                    node.name in model.never_raise_funcs
                    if owner is None
                    else node.name
                    in model.never_raise_methods.get(owner, set())
                )
                if annotated:
                    findings.extend(
                        NeverRaiseChecker(model, owner, node).run()
                    )
            elif isinstance(node, ast.ClassDef):
                scan(node.body, node.name)

    scan(model.tree.body, None)
    return findings


# -- driver -----------------------------------------------------------------


def analyze_file(path: str) -> Tuple[List[Finding], Dict[str, int]]:
    with open(path, "r") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return (
            [Finding(path, e.lineno or 0, 0, "CC000", f"syntax error: {e.msg}")],
            {"cache_keys": 0, "derived": 0, "never_raises": 0},
        )
    lines = source.splitlines()
    model = ModuleModel(path, tree, lines)
    findings: List[Finding] = []

    # CC001 over every function scope (and the module body); a site is
    # analyzed exactly once, in its innermost enclosing function, whose
    # assignment map is what resolves the baked/covered names
    def run_sites(func, cls, body):
        fs = FunctionSites(model, cls, func, body)
        fs.check()
        findings.extend(fs.findings)

    owning_class: Dict[int, ast.ClassDef] = {}
    for c in model.classes.values():
        for node in ast.walk(c):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                owning_class.setdefault(id(node), c)

    run_sites(tree, None, tree.body)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            run_sites(node, owning_class.get(id(node)), node.body)

    findings.extend(check_cc002(model))
    findings.extend(check_cc003(model))
    findings.extend(check_cc004(model))
    findings.extend(check_cc005(model))

    stats = {
        "cache_keys": model.n_cache_keys,
        "derived": model.n_derived,
        "never_raises": model.n_never_raises,
    }
    return _suppress(findings, lines), stats


def _suppress(findings: List[Finding], lines: List[str]) -> List[Finding]:
    return _core_suppress(findings, lines, _IGNORE_RE)


def lint_paths(paths: List[str]) -> Tuple[List[Finding], Dict[str, int]]:
    findings: List[Finding] = []
    totals = {"cache_keys": 0, "derived": 0, "never_raises": 0}
    files = iter_py_files(paths)
    for path in files:
        f, stats = analyze_file(path)
        findings.extend(f)
        for k in totals:
            totals[k] += stats[k]
    totals["files"] = len(files)
    totals["findings"] = len(findings)
    totals["annotations"] = (
        totals["cache_keys"] + totals["derived"] + totals["never_raises"]
    )
    return findings, totals


DEFAULT_PATHS = [
    "cyclonus_tpu/engine",
    "cyclonus_tpu/serve",
    "cyclonus_tpu/chaos",
]


def main(argv: Optional[List[str]] = None) -> int:
    return run_cli(
        "cachelint",
        __doc__,
        lint_paths,
        DEFAULT_PATHS,
        lambda findings, stats: (
            f"cachelint: {stats['findings']} finding(s), "
            f"{stats['cache_keys']} cache-key / {stats['derived']} "
            f"derived-from / {stats['never_raises']} never-raises "
            f"annotation(s) in {stats['files']} file(s)"
        ),
        argv,
    )


if __name__ == "__main__":
    sys.exit(main())
