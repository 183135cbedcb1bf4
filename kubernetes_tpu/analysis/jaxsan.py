"""jaxsan device-path linter: AST walk of everything reachable from the
JIT entry points, flagging hazards that break the static-program contract.

Why a bespoke linter instead of flake8 plugins: the hazards here are not
syntactic — `if x:` is fine on the host and a trace-time crash (or a
silently baked-in constant) on a traced value; `np.zeros(n)` is fine in
`build_dev` and a retrace bomb inside `_run_batch_impl`. Telling the two
apart requires (a) knowing WHICH functions execute under `jax.jit` — the
call-graph closure of the jitted impls behind the public entries (every
function declared with `@device_entry`, perf/ledger.py) — and (b) knowing
WHICH values are traced inside them — `fam` is a static argname and `if fam.spr_f:` is the intended
kernel-trimming idiom, while the same branch on `mask` would be a bug.

The analyzer therefore does a light interprocedural dataflow:

1. load every module of the target package, index functions, imports and
   NamedTuple definitions;
2. discover jit ROOTS — functions wrapped by `jax.jit(...)` (direct call,
   `functools.partial(jax.jit, ...)` decorator, or factory pattern) —
   with their `static_argnames`/`static_argnums`/`donate_argnums`;
3. propagate static-vs-traced levels through the call graph to a
   fixpoint: a root's static argnames seed STATIC params, everything
   else traced; each resolved call site pushes its argument levels onto
   the callee's params (traced wins);
4. run the traced-region rules (traced-branch, np-in-jit, dynamic-shape,
   tracer-leak, nondeterministic-iteration) over every reachable
   function with its inferred param levels, and the host-side rules
   (donation-after-use, plus set-iteration feeding tensor construction)
   over every function in the package.

Values are classified on a two-axis level: `traced` (device value) and
`structural` (a NamedTuple/tuple OF traced arrays — iterating or
checking `is None` on the container is trace-safe even though its leaves
are not). Annotations drive structure: any parameter or return annotated
with a NamedTuple class defined in the package is structural.

The output is a list of findings.Finding; inline `# jaxsan: waive[rule]`
comments baseline intentional exceptions (see findings.py).
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from .findings import Finding, RULES

# the decorator that declares a device entry (perf/ledger.py): every
# function it decorates is an entry point, and its `donates=` names the
# argument the entry donates
ENTRY_DECORATOR = "device_entry"

# attribute reads that always yield host-static values, even on tracers
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "_fields"}

# jnp/np constructors whose SHAPE argument(s) must be static
# (name → indices of positional shape args; () = every positional arg)
_SHAPE_FUNCS = {
    "zeros": (0,), "ones": (0,), "full": (0,), "empty": (0,),
    "arange": (), "linspace": (0, 1, 2), "eye": (0, 1),
    "reshape": (1,), "broadcast_to": (1,), "tile": (1,),
    "iota": (1,),
}

# python builtins that coerce a tracer to bool internally
_BOOL_BUILTINS = {"min", "max", "any", "all", "sorted"}

_MUTATORS = {"append", "extend", "add", "insert", "update", "setdefault",
             "appendleft"}


@dataclass(frozen=True)
class Level:
    """Two-axis value classification (see module docstring)."""

    traced: bool = False
    structural: bool = False

    def merge(self, other: "Level") -> "Level":
        return Level(self.traced or other.traced,
                     self.structural or other.structural)


STATIC = Level(False, False)
TRACED = Level(True, False)
STRUCT = Level(True, True)


@dataclass
class ModuleInfo:
    name: str                     # dotted module name
    path: str                     # path relative to the analysis root
    tree: ast.Module
    source: str
    funcs: dict = field(default_factory=dict)        # name → FunctionDef
    imports: dict = field(default_factory=dict)      # alias → dotted target
    import_objects: dict = field(default_factory=dict)  # alias → (mod, obj)
    namedtuples: dict = field(default_factory=dict)  # class → {field: ann}
    constants: set = field(default_factory=set)      # module-level names


@dataclass
class FnInfo:
    module: ModuleInfo
    name: str
    node: ast.FunctionDef
    is_root: bool = False
    static_params: set = field(default_factory=set)
    donated_params: set = field(default_factory=set)
    traced: bool = False          # reachable from a jit root
    param_levels: dict = field(default_factory=dict)

    @property
    def qualname(self) -> str:
        return f"{self.module.name}.{self.name}"

    def params(self) -> list[str]:
        a = self.node.args
        return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
                + [p.arg for p in a.kwonlyargs])


def _dotted(node: ast.AST) -> str | None:
    """a.b.c attribute/name chain as a dotted string (None if dynamic)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class JaxsanAnalyzer:
    """Package-wide device-path linter (see module docstring)."""

    def __init__(self, root: str, package: str = "kubernetes_tpu",
                 entry_points: dict | None = None,
                 donating: dict | None = None):
        self.root = root
        self.package = package
        # None = the `@device_entry` declarations found by load()
        self.entry_points = entry_points
        self.donating = donating
        # qualname → (kernel label, donated position) per declaration
        self.declared: dict[str, tuple] = {}
        self.modules: dict[str, ModuleInfo] = {}
        self.fns: dict[str, FnInfo] = {}          # qualname → FnInfo
        self.findings: list[Finding] = []
        self.missing_entries: list[str] = []

    # -- loading --------------------------------------------------------------

    def load(self) -> "JaxsanAnalyzer":
        pkg_dir = os.path.join(self.root, *self.package.split("."))
        for dirpath, _dirs, files in os.walk(pkg_dir):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, self.root)
                mod = rel[:-3].replace(os.sep, ".")
                if mod.endswith(".__init__"):
                    mod = mod[: -len(".__init__")]
                with open(path) as f:
                    source = f.read()
                try:
                    tree = ast.parse(source, filename=rel)
                except SyntaxError as e:  # pragma: no cover - broken file
                    self.findings.append(Finding(
                        rule="traced-branch", path=rel,
                        line=e.lineno or 1,
                        message=f"unparseable module: {e.msg}"))
                    continue
                self.modules[mod] = ModuleInfo(name=mod, path=rel,
                                               tree=tree, source=source)
        for mi in self.modules.values():
            self._index_module(mi)
        self._discover_entries()
        self._discover_roots()
        self._propagate()
        return self

    def _index_module(self, mi: ModuleInfo) -> None:
        pkg_parts = mi.name.split(".")
        for node in mi.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                mi.funcs[node.name] = node
                self.fns[f"{mi.name}.{node.name}"] = FnInfo(
                    module=mi, name=node.name, node=node)
            elif isinstance(node, ast.ClassDef):
                bases = {(_dotted(b) or "").split(".")[-1]
                         for b in node.bases}
                if "NamedTuple" in bases:
                    fields = {}
                    for item in node.body:
                        if isinstance(item, ast.AnnAssign) and isinstance(
                                item.target, ast.Name):
                            fields[item.target.id] = _dotted(
                                item.annotation) or ""
                    mi.namedtuples[node.name] = fields
                mi.constants.add(node.name)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        mi.constants.add(t.id)
            elif isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name):
                mi.constants.add(node.target.id)
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    mi.imports[alias.asname or alias.name.split(".")[0]] = \
                        alias.name
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                if node.level:
                    base = pkg_parts[: len(pkg_parts) - node.level]
                    target = ".".join(base + ([node.module]
                                              if node.module else []))
                else:
                    target = node.module or ""
                for alias in node.names:
                    name = alias.asname or alias.name
                    if f"{target}.{alias.name}" in self.modules:
                        mi.imports[name] = f"{target}.{alias.name}"
                    else:
                        mi.import_objects[name] = (target, alias.name)

    # -- namedtuple / annotation helpers --------------------------------------

    def _is_namedtuple(self, name: str | None) -> bool:
        if not name:
            return False
        tail = name.split(".")[-1].split("|")[0].strip()
        return any(tail in mi.namedtuples for mi in self.modules.values())

    def _annotation_level(self, ann: ast.AST | None) -> Level | None:
        if ann is None:
            return None
        text = None
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            text = ann.value
        else:
            text = _dotted(ann)
            if text is None and isinstance(ann, ast.BinOp):
                # X | None
                text = _dotted(ann.left)
            if text is None and isinstance(ann, ast.Subscript):
                text = _dotted(ann.value)
        if text is None:
            return None
        tail = text.split("[")[0].split("|")[0].strip().split(".")[-1]
        if tail in ("int", "float", "bool", "str", "tuple", "list", "dict"):
            return STATIC
        if self._is_namedtuple(tail):
            return STRUCT
        return None

    # -- jit root discovery ---------------------------------------------------

    def _resolve_fn(self, mi: ModuleInfo, node: ast.AST) -> FnInfo | None:
        """Resolve a callee expression to an indexed function."""
        if isinstance(node, ast.Name):
            if node.id in mi.funcs:
                return self.fns.get(f"{mi.name}.{node.id}")
            obj = mi.import_objects.get(node.id)
            if obj and obj[0] in self.modules:
                return self.fns.get(f"{obj[0]}.{obj[1]}")
        elif isinstance(node, ast.Attribute):
            base = _dotted(node.value)
            if base and base in mi.imports:
                target = mi.imports[base]
                if target in self.modules:
                    return self.fns.get(f"{target}.{node.attr}")
        return None

    @staticmethod
    def _const_names(node: ast.AST | None) -> set:
        out = set()
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            out.add(node.value)
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
        return out

    @staticmethod
    def _const_ints(node: ast.AST | None) -> set:
        out = set()
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            out.add(node.value)
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.add(e.value)
        elif isinstance(node, ast.IfExp):
            for side in (node.body, node.orelse):
                out |= JaxsanAnalyzer._const_ints(side)
        return out

    def _mark_root(self, fi: FnInfo, static_names: set, static_nums: set,
                   donate_nums: set) -> None:
        fi.is_root = True
        fi.traced = True
        params = fi.params()
        fi.static_params |= static_names
        for i in static_nums:
            if 0 <= i < len(params):
                fi.static_params.add(params[i])
        for i in donate_nums:
            if 0 <= i < len(params):
                fi.donated_params.add(params[i])
        for p in params:
            lvl = STATIC if p in fi.static_params else TRACED
            if lvl.traced:
                ann = self._param_annotation(fi, p)
                alvl = self._annotation_level(ann)
                if alvl is not None and alvl.structural:
                    lvl = STRUCT
            fi.param_levels[p] = fi.param_levels.get(p, STATIC).merge(lvl) \
                if p not in fi.static_params else STATIC

    @staticmethod
    def _param_annotation(fi: FnInfo, name: str) -> ast.AST | None:
        a = fi.node.args
        for p in a.posonlyargs + a.args + a.kwonlyargs:
            if p.arg == name:
                return p.annotation
        return None

    def _discover_entries(self) -> None:
        """Read every `@device_entry(...)` declaration; unless the caller
        gave them, they are the entry points and the donating entries
        (donated param index and name in the public signature)."""
        entries: dict[str, list] = {}
        donating: dict[str, tuple] = {}
        for fi in self.fns.values():
            for dec in fi.node.decorator_list:
                if not isinstance(dec, ast.Call) or (
                        _dotted(dec.func) or "").split(".")[-1] \
                        != ENTRY_DECORATOR:
                    continue
                label = fi.name
                if dec.args and isinstance(dec.args[0], ast.Constant):
                    label = dec.args[0].value
                donates = None
                for kw in dec.keywords:
                    if kw.arg == "donates" and isinstance(
                            kw.value, ast.Constant):
                        donates = kw.value.value
                self.declared[fi.qualname] = (label, donates)
                entries.setdefault(fi.module.name, []).append(fi.name)
                if donates is not None:
                    donating[fi.name] = (donates, fi.params()[donates])
        if self.entry_points is None:
            self.entry_points = {m: tuple(ns) for m, ns in entries.items()}
        if self.donating is None:
            self.donating = donating

    def _discover_roots(self) -> None:
        for mi in self.modules.values():
            for node in ast.walk(mi.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._root_from_decorators(mi, node)
                elif isinstance(node, ast.Call):
                    self._root_from_call(mi, node)

    def _jit_call_opts(self, call: ast.Call):
        names, nums, dons = set(), set(), set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                names |= self._const_names(kw.value)
            elif kw.arg == "static_argnums":
                nums |= self._const_ints(kw.value)
            elif kw.arg == "donate_argnums":
                dons |= self._const_ints(kw.value)
        return names, nums, dons

    def _root_from_call(self, mi: ModuleInfo, call: ast.Call) -> None:
        name = _dotted(call.func) or ""
        tail = name.split(".")[-1]
        if tail != "jit" or not call.args:
            return
        fi = self._resolve_fn(mi, call.args[0])
        if fi is None:
            return
        names, nums, dons = self._jit_call_opts(call)
        self._mark_root(fi, names, nums, dons)

    def _root_from_decorators(self, mi: ModuleInfo,
                              node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            fi = self.fns.get(f"{mi.name}.{node.name}")
            if fi is None:
                continue
            if isinstance(dec, (ast.Name, ast.Attribute)):
                if (_dotted(dec) or "").split(".")[-1] == "jit":
                    self._mark_root(fi, set(), set(), set())
            elif isinstance(dec, ast.Call):
                dn = _dotted(dec.func) or ""
                if dn.split(".")[-1] == "jit":
                    names, nums, dons = self._jit_call_opts(dec)
                    self._mark_root(fi, names, nums, dons)
                elif dn.split(".")[-1] == "partial" and dec.args:
                    inner = _dotted(dec.args[0]) or ""
                    if inner.split(".")[-1] == "jit":
                        names, nums, dons = self._jit_call_opts(dec)
                        self._mark_root(fi, names, nums, dons)

    # -- interprocedural propagation ------------------------------------------

    def _propagate(self) -> None:
        work = [fi for fi in self.fns.values() if fi.is_root]
        seen_edges = set()
        while work:
            fi = work.pop()
            checker = _FnChecker(self, fi, collect=False)
            checker.run()
            for callee, arg_levels in checker.calls:
                key = (fi.qualname, callee.qualname,
                       tuple(sorted((k, v.traced, v.structural)
                                    for k, v in arg_levels.items())))
                if key in seen_edges:
                    continue
                seen_edges.add(key)
                changed = not callee.traced
                callee.traced = True
                for pname, lvl in arg_levels.items():
                    ann = self._annotation_level(
                        self._param_annotation(callee, pname))
                    if ann is not None:
                        if ann is STATIC and not lvl.traced:
                            lvl = STATIC
                        elif ann.structural and lvl.traced:
                            lvl = lvl.merge(Level(True, True))
                    old = callee.param_levels.get(pname, STATIC)
                    new = old.merge(lvl)
                    if new != old:
                        callee.param_levels[pname] = new
                        changed = True
                if changed and not callee.is_root:
                    work.append(callee)

    # -- entry coverage -------------------------------------------------------

    def check_entry_coverage(self) -> list[str]:
        """Each declared JIT entry must exist and transitively reach at
        least one discovered jit root — otherwise the lint has silently
        lost device-path coverage."""
        missing = []
        for mod, names in self.entry_points.items():
            mi = self.modules.get(mod)
            for name in names:
                fi = self.fns.get(f"{mod}.{name}") if mi else None
                if fi is None or not self._reaches_root(fi, set()):
                    missing.append(f"{mod}.{name}")
        self.missing_entries = missing
        return missing

    def _reaches_root(self, fi: FnInfo, seen: set) -> bool:
        if fi.qualname in seen:
            return False
        seen.add(fi.qualname)
        if fi.is_root:
            return True
        for node in ast.walk(fi.node):
            target = None
            if isinstance(node, ast.Call):
                target = self._resolve_fn(fi.module, node.func)
                if target is None and node.args:
                    # factory pattern: jax.jit(impl) referenced as arg
                    target = self._resolve_fn(fi.module, node.args[0])
            elif isinstance(node, ast.Name):
                target = self._resolve_fn(fi.module, node)
            if target is not None and self._reaches_root(target, seen):
                return True
        return False

    # -- rule passes ----------------------------------------------------------

    def run(self) -> list[Finding]:
        self.check_entry_coverage()
        for fi in self.fns.values():
            if fi.traced:
                _FnChecker(self, fi, collect=True).run()
            else:
                _HostChecker(self, fi).run()
        return self.findings

    def emit(self, rule: str, fi: FnInfo, node: ast.AST,
             message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=fi.module.path,
            line=getattr(node, "lineno", 1), message=message,
            func=fi.qualname.split(".")[-1]))


# ---------------------------------------------------------------------------
# per-function abstract interpretation


class _FnChecker:
    """Sequentially interprets one traced function's body, tracking
    name → Level, emitting findings (when `collect`) and recording
    resolved call edges with argument levels (for propagation)."""

    def __init__(self, an: JaxsanAnalyzer, fi: FnInfo, collect: bool,
                 parent_env: dict | None = None,
                 parent_locals: set | None = None):
        self.an = an
        self.fi = fi
        self.collect = collect
        self.env: dict[str, Level] = dict(parent_env or {})
        self.outer_names = set(self.env) | (parent_locals or set())
        self.local_names: set[str] = set()
        self.nonlocal_names: set[str] = set()
        self.set_names: set[str] = set()
        self.calls: list[tuple[FnInfo, dict]] = []
        self.nested: dict[str, ast.FunctionDef] = {}
        self._nested_done: set[str] = set()

    # -- env helpers ----------------------------------------------------------

    def run(self) -> None:
        for p in self.fi.params():
            # missing level = the fixpoint never saw this param at a call
            # site (a default-only argument): its default expression is a
            # host constant, so STATIC. Roots and nested callbacks are
            # explicitly seeded (TRACED) before reaching here — an
            # optimistic default keeps one early conservative guess from
            # monotonically poisoning the whole call graph.
            self.env[p] = self.fi.param_levels.get(p, STATIC)
            self.local_names.add(p)
        self.block(self.fi.node.body)
        # nested defs never directly called (callbacks handed to lax /
        # shard_map / unknown callees) get a conservative all-traced pass
        for name, node in self.nested.items():
            if name not in self._nested_done:
                self._analyze_nested(node, {})

    def bind(self, target: ast.AST, lvl: Level,
             iter_src: ast.AST | None = None) -> None:
        if isinstance(target, ast.Name):
            if target.id in self.nonlocal_names:
                if lvl.traced and self.collect:
                    self.an.emit("tracer-leak", self.fi, target,
                                 f"traced value assigned to nonlocal/global "
                                 f"'{target.id}'")
            self.env[target.id] = self.env.get(
                target.id, STATIC).merge(lvl) if lvl.traced else lvl
            self.local_names.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            # positional zip() match lets `for name, arr in zip(fields, t)`
            # keep the static element static
            zip_args = None
            if (iter_src is not None and isinstance(iter_src, ast.Call)
                    and (_dotted(iter_src.func) or "") == "zip"
                    and len(iter_src.args) == len(target.elts)):
                zip_args = [self.level(a) for a in iter_src.args]
            for i, e in enumerate(target.elts):
                elvl = zip_args[i] if zip_args is not None else (
                    Level(lvl.traced, False))
                self.bind(e, elvl)
        elif isinstance(target, ast.Starred):
            self.bind(target.value, lvl)
        elif isinstance(target, ast.Attribute):
            if lvl.traced and self.collect:
                self.an.emit("tracer-leak", self.fi, target,
                             f"traced value stored on attribute "
                             f"'{_dotted(target) or target.attr}'")
        elif isinstance(target, ast.Subscript):
            base = target.value
            if (lvl.traced and self.collect and isinstance(base, ast.Name)
                    and base.id not in self.local_names
                    and not self.env.get(base.id, STATIC).traced):
                self.an.emit("tracer-leak", self.fi, target,
                             f"traced value stored into outer container "
                             f"'{base.id}'")

    def name_level(self, name: str) -> Level:
        if name in self.env:
            return self.env[name]
        return STATIC   # module constants / builtins / unknown → static

    # -- statements -----------------------------------------------------------

    def block(self, body: list) -> None:
        for stmt in body:
            self.stmt(stmt)

    def stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.nested[node.name] = node
            self.local_names.add(node.name)
            self.env[node.name] = STATIC
        elif isinstance(node, ast.Return):
            if node.value is not None:
                self.level(node.value)
        elif isinstance(node, ast.Expr):
            self.level(node.value)
        elif isinstance(node, ast.Assign):
            lvl = self.level(node.value)
            for t in node.targets:
                self.bind(t, lvl, iter_src=node.value)
            self._note_set_assign(node.targets, node.value)
        elif isinstance(node, ast.AugAssign):
            lvl = self.level(node.value)
            if isinstance(node.target, ast.Name):
                cur = self.env.get(node.target.id, STATIC)
                self.bind(node.target, cur.merge(lvl))
            else:
                self.bind(node.target, lvl)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self.bind(node.target, self.level(node.value))
        elif isinstance(node, ast.If):
            self._bool_context(node.test, "if")
            self.block(node.body)
            self.block(node.orelse)
        elif isinstance(node, ast.While):
            self._bool_context(node.test, "while")
            self.block(node.body)
            self.block(node.body)     # second pass: stabilize loop levels
            self.block(node.orelse)
        elif isinstance(node, ast.For):
            self._check_iteration(node.iter)
            it = self.level(node.iter)
            self.bind(node.target, Level(it.traced, False),
                      iter_src=node.iter)
            self.block(node.body)
            self.block(node.body)
            self.block(node.orelse)
        elif isinstance(node, ast.With):
            for item in node.items:
                self.level(item.context_expr)
                if item.optional_vars is not None:
                    self.bind(item.optional_vars, STATIC)
            self.block(node.body)
        elif isinstance(node, ast.Try):
            self.block(node.body)
            for h in node.handlers:
                if h.name:
                    self.local_names.add(h.name)
                    self.env[h.name] = STATIC
                self.block(h.body)
            self.block(node.orelse)
            self.block(node.finalbody)
        elif isinstance(node, ast.Assert):
            self._bool_context(node.test, "assert")
            if node.msg is not None:
                self.level(node.msg)
        elif isinstance(node, (ast.Global, ast.Nonlocal)):
            self.nonlocal_names.update(node.names)
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self.level(node.exc)
        elif isinstance(node, ast.Delete):
            pass
        elif isinstance(node, ast.ClassDef):
            self.local_names.add(node.name)

    def _note_set_assign(self, targets, value) -> None:
        is_set = isinstance(value, ast.Set) or (
            isinstance(value, ast.Call)
            and (_dotted(value.func) or "") in ("set", "frozenset")) or \
            isinstance(value, ast.SetComp)
        for t in targets:
            if isinstance(t, ast.Name):
                if is_set:
                    self.set_names.add(t.id)
                else:
                    self.set_names.discard(t.id)

    # -- bool / iteration contexts --------------------------------------------

    def _bool_context(self, test: ast.AST, kind: str) -> None:
        lvl = self.level(test)
        if lvl.traced and not lvl.structural and self.collect:
            self.an.emit("traced-branch", self.fi, test,
                         f"Python `{kind}` on a traced value "
                         f"(`{ast.unparse(test)[:60]}`)")

    def _check_iteration(self, it: ast.AST) -> None:
        lvl = self.level(it)
        if not self.collect:
            return
        if self._is_set_expr(it):
            self.an.emit("nondeterministic-iteration", self.fi, it,
                         "iteration over an unordered set inside traced "
                         "code (trace order bakes into the program)")
        elif lvl.traced and not lvl.structural:
            self.an.emit("traced-branch", self.fi, it,
                         f"Python loop over a traced value "
                         f"(`{ast.unparse(it)[:60]}`)")

    def _is_set_expr(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call) and \
                (_dotted(node.func) or "") in ("set", "frozenset"):
            return True
        return isinstance(node, ast.Name) and node.id in self.set_names

    # -- expressions ----------------------------------------------------------

    def level(self, node: ast.AST) -> Level:   # noqa: C901 - dispatch table
        if node is None or isinstance(node, ast.Constant):
            return STATIC
        if isinstance(node, ast.Name):
            return self.name_level(node.id)
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                self.level(node.value)
                return STATIC
            base = self.level(node.value)
            if base.structural:
                # NamedTuple field: another NamedTuple → structural leaf
                return STRUCT if self._field_is_struct(node) else TRACED
            return Level(base.traced, False)
        if isinstance(node, ast.Subscript):
            v = self.level(node.value)
            s = self.level(node.slice)
            if not v.traced:
                return Level(s.traced, False)
            return Level(True, False)
        if isinstance(node, (ast.Tuple, ast.List)):
            lv = STATIC
            for e in node.elts:
                lv = lv.merge(self.level(e))
            return Level(lv.traced, lv.traced)   # containers are structural
        if isinstance(node, ast.Dict):
            lv = STATIC
            for k, v in zip(node.keys, node.values):
                if k is not None:
                    lv = lv.merge(self.level(k))
                lv = lv.merge(self.level(v))
            return Level(lv.traced, lv.traced)
        if isinstance(node, ast.Set):
            for e in node.elts:
                self.level(e)
            return STATIC
        if isinstance(node, ast.BoolOp):
            lv = STATIC
            for v in node.values:
                vl = self.level(v)
                if vl.traced and not vl.structural and self.collect:
                    self.an.emit("traced-branch", self.fi, v,
                                 "`and`/`or` coerces a traced value to "
                                 "bool (use & / | / jnp.logical_*)")
                lv = lv.merge(vl)
            return Level(lv.traced, False)
        if isinstance(node, ast.UnaryOp):
            lv = self.level(node.operand)
            if isinstance(node.op, ast.Not) and lv.traced \
                    and not lv.structural and self.collect:
                self.an.emit("traced-branch", self.fi, node,
                             "`not` coerces a traced value to bool "
                             "(use ~ / jnp.logical_not)")
            return Level(lv.traced, False)
        if isinstance(node, ast.BinOp):
            return Level(self.level(node.left).traced
                         | self.level(node.right).traced, False)
        if isinstance(node, ast.Compare):
            if self._is_none_check(node):
                self.level(node.left)
                return STATIC
            lv = self.level(node.left)
            for c in node.comparators:
                lv = lv.merge(self.level(c))
            return Level(lv.traced, False)
        if isinstance(node, ast.IfExp):
            self._bool_context(node.test, "conditional expression")
            return self.level(node.body).merge(self.level(node.orelse))
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self._comp(node, node.elt)
        if isinstance(node, ast.DictComp):
            return self._comp(node, node.value, key=node.key)
        if isinstance(node, ast.Lambda):
            return STATIC
        if isinstance(node, ast.Starred):
            return self.level(node.value)
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    self.level(v.value)
            return STATIC
        if isinstance(node, ast.Slice):
            lv = STATIC
            for part in (node.lower, node.upper, node.step):
                if part is not None:
                    lv = lv.merge(self.level(part))
            return lv
        if isinstance(node, ast.NamedExpr):
            lv = self.level(node.value)
            self.bind(node.target, lv)
            return lv
        return STATIC

    @staticmethod
    def _is_none_check(node: ast.Compare) -> bool:
        return (len(node.ops) == 1
                and isinstance(node.ops[0], (ast.Is, ast.IsNot))
                and isinstance(node.comparators[0], ast.Constant)
                and node.comparators[0].value is None)

    def _field_is_struct(self, node: ast.Attribute) -> bool:
        # best effort: field annotation of any known NamedTuple with this
        # field name resolving to another NamedTuple
        for mi in self.an.modules.values():
            for fields in mi.namedtuples.values():
                ann = fields.get(node.attr)
                if ann and self.an._is_namedtuple(ann):
                    return True
        return False

    def _comp(self, node, elt, key=None) -> Level:
        if self.collect:
            for gen in node.generators:
                self._check_iteration(gen.iter)
        lv = STATIC
        for gen in node.generators:
            it = self.level(gen.iter)
            self.bind(gen.target, Level(it.traced, False),
                      iter_src=gen.iter)
            for cond in gen.ifs:
                self._bool_context(cond, "comprehension filter")
        lv = lv.merge(self.level(elt))
        if key is not None:
            lv = lv.merge(self.level(key))
        return Level(lv.traced, lv.traced)

    # -- calls ----------------------------------------------------------------

    def _call(self, node: ast.Call) -> Level:   # noqa: C901
        fname = _dotted(node.func) or ""
        tail = fname.split(".")[-1]
        root = fname.split(".")[0] if fname else ""
        arg_levels = [self.level(a) for a in node.args]
        kw_levels = {kw.arg: self.level(kw.value) for kw in node.keywords
                     if kw.arg is not None}
        for kw in node.keywords:
            if kw.arg is None:
                self.level(kw.value)
        any_traced = any(l.traced for l in arg_levels) or \
            any(l.traced for l in kw_levels.values())

        # numpy inside traced code
        if self.collect and root and self._is_numpy_root(root) \
                and isinstance(node.func, ast.Attribute):
            self.an.emit("np-in-jit", self.fi, node,
                         f"`{fname}` call inside traced code")
            return TRACED

        device_lib = self._is_device_root(root)

        # casts / bool-coercing builtins
        if fname in ("int", "float", "bool") and arg_levels and \
                arg_levels[0].traced and not arg_levels[0].structural:
            if self.collect:
                self.an.emit("traced-branch", self.fi, node,
                             f"host `{fname}()` cast forces a traced value "
                             "to a Python scalar")
            return TRACED
        if fname in _BOOL_BUILTINS and any(
                l.traced and not l.structural for l in arg_levels):
            if self.collect:
                self.an.emit("traced-branch", self.fi, node,
                             f"builtin `{fname}()` on a traced value "
                             "coerces to bool internally")
            return TRACED

        # dynamic shapes
        if self.collect and tail in _SHAPE_FUNCS and (
                device_lib or isinstance(node.func, ast.Attribute)):
            self._check_shapes(node, tail, arg_levels, kw_levels)

        # leaks into outer containers
        if self.collect and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS and any_traced:
            base = node.func.value
            if isinstance(base, ast.Name) \
                    and base.id not in self.local_names \
                    and not self.env.get(base.id, STATIC).traced:
                self.an.emit("tracer-leak", self.fi, node,
                             f"traced value accumulated into outer "
                             f"container '{base.id}.{node.func.attr}'")

        # interprocedural edges
        callee = self.an._resolve_fn(self.fi.module, node.func)
        if callee is not None:
            self._record_edge(callee, node, arg_levels, kw_levels)
            ann = self.an._annotation_level(callee.node.returns)
            if ann is not None:
                return ann if not any_traced or ann is STATIC else ann
            return Level(True, False) if (any_traced or callee.traced) \
                else STATIC

        # functools.partial(F, ...): propagate bound args, rest traced
        if tail == "partial" and node.args:
            pf = self.an._resolve_fn(self.fi.module, node.args[0])
            if pf is not None:
                self._record_partial(pf, node, arg_levels[1:], kw_levels)
                return STATIC
        # nested function usage
        if isinstance(node.func, ast.Name) and node.func.id in self.nested:
            self._analyze_nested(
                self.nested[node.func.id],
                self._map_args(self.nested[node.func.id], node,
                               arg_levels, kw_levels))
            return TRACED if any_traced else STATIC
        # callbacks handed to lax.scan / while_loop / cond / shard_map /
        # vmap / indexed callees: their params are traced
        for a in node.args:
            if isinstance(a, ast.Name) and a.id in self.nested:
                self._analyze_nested(self.nested[a.id], {})
            elif isinstance(a, ast.Lambda):
                self._analyze_lambda(a)
        for kw in node.keywords:
            if isinstance(kw.value, ast.Name) and kw.value.id in self.nested:
                self._analyze_nested(self.nested[kw.value.id], {})
            elif isinstance(kw.value, ast.Lambda):
                self._analyze_lambda(kw.value)

        if device_lib:
            if tail in ("iinfo", "finfo"):
                return STATIC
            return TRACED
        # method call on a traced object (x.astype, x.at[...].set, ...)
        if isinstance(node.func, ast.Attribute):
            base = self.level(node.func.value)
            if base.traced:
                if node.func.attr == "_replace":
                    merged = base
                    for lv in kw_levels.values():
                        merged = merged.merge(Level(lv.traced, False))
                    return Level(True, base.structural)
                return Level(True, False)
        if fname == "getattr":
            return Level(bool(arg_levels) and arg_levels[0].traced, False)
        if fname in ("len", "isinstance", "hasattr", "type",
                     "range", "enumerate", "repr", "str", "id", "format"):
            return STATIC
        if fname == "zip":
            return Level(any_traced, True)
        return Level(any_traced, any_traced)

    def _is_numpy_root(self, root: str) -> bool:
        target = self.fi.module.imports.get(root, "")
        return target == "numpy" or target.startswith("numpy.")

    def _is_device_root(self, root: str) -> bool:
        if not root:
            return False
        target = self.fi.module.imports.get(root, "")
        if target == "jax" or target.startswith("jax."):
            return True
        if root in ("jnp", "lax", "jax"):
            return True
        obj = self.fi.module.import_objects.get(root)
        return bool(obj and obj[0].startswith("jax"))

    def _check_shapes(self, node: ast.Call, tail: str, arg_levels,
                      kw_levels) -> None:
        idxs = _SHAPE_FUNCS[tail]
        shape_args = (list(range(len(arg_levels))) if idxs == ()
                      else [i for i in idxs if i < len(arg_levels)])
        # method form a.reshape(...): every positional arg is shape
        if isinstance(node.func, ast.Attribute) and \
                self.level(node.func.value).traced and \
                tail in ("reshape", "broadcast_to", "tile"):
            shape_args = list(range(len(arg_levels)))
        for i in shape_args:
            if arg_levels[i].traced:
                self.an.emit("dynamic-shape", self.fi, node,
                             f"`{tail}` shape argument derives from a "
                             "traced value")
                return
        lv = kw_levels.get("shape")
        if lv is not None and lv.traced:
            self.an.emit("dynamic-shape", self.fi, node,
                         f"`{tail}` shape= derives from a traced value")

    # -- interprocedural plumbing --------------------------------------------

    def _map_args(self, target: ast.FunctionDef, node: ast.Call,
                  arg_levels, kw_levels) -> dict:
        a = target.args
        params = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        mapping: dict[str, Level] = {}
        for i, lvl in enumerate(arg_levels):
            if i < len(params):
                mapping[params[i]] = lvl
        for name, lvl in kw_levels.items():
            mapping[name] = lvl
        return mapping

    def _record_edge(self, callee: FnInfo, node: ast.Call, arg_levels,
                     kw_levels) -> None:
        mapping = self._map_args(callee.node, node, arg_levels, kw_levels)
        # a callable handed to an indexed callee will be invoked on
        # traced values — give nested callbacks the conservative pass
        for a in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(a, ast.Name) and a.id in self.nested:
                self._analyze_nested(self.nested[a.id], {})
            elif isinstance(a, ast.Lambda):
                self._analyze_lambda(a)
        self.calls.append((callee, mapping))

    def _record_partial(self, callee: FnInfo, node: ast.Call,
                        bound_levels, kw_levels) -> None:
        params = callee.params()
        mapping: dict[str, Level] = {}
        for i, lvl in enumerate(bound_levels):
            if i < len(params):
                mapping[params[i]] = lvl
        for name, lvl in kw_levels.items():
            if name in params:
                mapping[name] = lvl
        for p in params:
            mapping.setdefault(p, TRACED)
        self.calls.append((callee, mapping))

    def _analyze_nested(self, node: ast.FunctionDef,
                        param_levels: dict) -> None:
        key = f"{node.name}:{node.lineno}"
        if key in self._nested_done:
            return
        self._nested_done.add(key)
        if node.name in self.nested:
            self._nested_done.add(node.name)
        sub_fi = FnInfo(module=self.fi.module, name=node.name, node=node,
                        traced=True)
        for p in sub_fi.params():
            lvl = param_levels.get(p)
            if lvl is None:
                lvl = TRACED
                ann = self.an._annotation_level(
                    JaxsanAnalyzer._param_annotation(sub_fi, p))
                if ann is not None:
                    lvl = STRUCT if ann.structural else \
                        (STATIC if ann is STATIC else TRACED)
            sub_fi.param_levels[p] = lvl
        sub = _FnChecker(self.an, sub_fi, self.collect,
                         parent_env=self.env,
                         parent_locals=self.local_names)
        sub.run()
        self.calls.extend(sub.calls)

    def _analyze_lambda(self, node: ast.Lambda) -> None:
        fn = ast.FunctionDef(
            name="<lambda>", args=node.args,
            body=[ast.Return(value=node.body, lineno=node.lineno,
                             col_offset=node.col_offset)],
            decorator_list=[], lineno=node.lineno,
            col_offset=node.col_offset)
        ast.fix_missing_locations(fn)
        self._analyze_nested(fn, {})


# ---------------------------------------------------------------------------
# host-side pass: donation-after-use + set-iteration feeding tensors


class _HostChecker:
    """Rules that apply to HOST functions: reading a donated carry after
    the donating dispatch, and unordered-set iteration that feeds tensor
    construction (parity-sensitive constants)."""

    ARRAY_CTORS = {"array", "asarray", "stack", "concatenate", "zeros",
                   "ones", "full", "fromiter"}

    def __init__(self, an: JaxsanAnalyzer, fi: FnInfo):
        self.an = an
        self.fi = fi

    def run(self) -> None:
        self._donation_pass(self.fi.node)
        self._set_iteration_pass()

    # -- donation-after-use ---------------------------------------------------

    def _donation_pass(self, fn: ast.FunctionDef) -> None:
        statements = list(ast.walk(fn))
        for body in self._bodies(fn):
            for i, stmt in enumerate(body):
                for call in ast.walk(stmt):
                    if not isinstance(call, ast.Call):
                        continue
                    entry = self._donating_entry(call)
                    if entry is None:
                        continue
                    donated = self._donated_arg(call, entry)
                    if not isinstance(donated, ast.Name):
                        continue
                    if self._rebinds(stmt, donated.id):
                        # `carry = run_batch(..., carry, ...)` — the
                        # donating statement rebinds the name to the
                        # returned carry, the blessed idiom
                        continue
                    self._check_after(body, i, donated.id, entry)
        del statements

    def _bodies(self, fn: ast.FunctionDef):
        for node in ast.walk(fn):
            for attr in ("body", "orelse", "finalbody"):
                body = getattr(node, attr, None)
                if isinstance(body, list) and body \
                        and isinstance(body[0], ast.stmt):
                    yield body

    def _donating_entry(self, call: ast.Call):
        name = (_dotted(call.func) or "").split(".")[-1]
        if name in self.an.donating:
            return name
        return None

    def _donated_arg(self, call: ast.Call, entry: str):
        idx, kwname = self.an.donating[entry]
        for kw in call.keywords:
            if kw.arg == kwname:
                return kw.value
        if idx < len(call.args):
            return call.args[idx]
        return None

    def _check_after(self, body: list, i: int, name: str,
                     entry: str) -> None:
        for stmt in body[i + 1:]:
            if self._rebinds(stmt, name):
                return
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) and node.id == name \
                        and isinstance(node.ctx, ast.Load):
                    self.an.emit(
                        "donation-after-use", self.fi, node,
                        f"'{name}' was donated to {entry}() and read "
                        "afterwards")
                    return

    @staticmethod
    def _rebinds(stmt: ast.stmt, name: str) -> bool:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) and n.id == name \
                            and isinstance(n.ctx, ast.Store):
                        return True
        if isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            t = stmt.target
            if isinstance(t, ast.Name) and t.id == name:
                return True
        return False

    # -- set iteration feeding tensor construction ----------------------------

    def _set_iteration_pass(self) -> None:
        for node in ast.walk(self.fi.node):
            it = None
            scope = None
            if isinstance(node, ast.For):
                it, scope = node.iter, node.body
            elif isinstance(node, (ast.ListComp, ast.GeneratorExp,
                                   ast.SetComp)):
                it = node.generators[0].iter
                scope = [node]
            if it is None or not self._is_set_expr(it):
                continue
            if self._feeds_tensor(scope):
                self.an.emit(
                    "nondeterministic-iteration", self.fi, it,
                    "unordered set iteration feeds tensor construction "
                    "(parity-sensitive order)")

    @staticmethod
    def _is_set_expr(node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        return isinstance(node, ast.Call) and \
            (_dotted(node.func) or "") in ("set", "frozenset")

    def _feeds_tensor(self, scope) -> bool:
        for stmt in scope or []:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    name = _dotted(node.func) or ""
                    parts = name.split(".")
                    if len(parts) >= 2 and parts[-1] in self.ARRAY_CTORS \
                            and parts[0] in ("np", "numpy", "jnp"):
                        return True
        return False


# ---------------------------------------------------------------------------
# convenience driver


def analyze_tree(root: str, package: str = "kubernetes_tpu",
                 entry_points: dict | None = None,
                 donating: dict | None = None,
                 with_locks: bool = True,
                 apply_waiver_comments: bool = True) -> list[Finding]:
    """Run the full static suite (device-path rules + lock discipline)
    over `root/package`, apply inline waivers, return all findings
    (waived ones included, flagged)."""
    from .findings import apply_waivers, parse_waivers
    from .locks import LockChecker

    an = JaxsanAnalyzer(root, package=package, entry_points=entry_points,
                        donating=donating).load()
    findings = an.run()
    for entry in an.missing_entries:
        findings.append(Finding(
            rule="traced-branch", path=package.replace(".", os.sep),
            line=1,
            message=f"JIT entry point {entry} not found or does not reach "
                    "a jitted function (lint coverage lost)"))
    if with_locks:
        findings.extend(LockChecker(an.modules).run())
    if apply_waiver_comments:
        waivers = {mi.path: parse_waivers(mi.source)
                   for mi in an.modules.values()}
        apply_waivers(findings, waivers)
    return findings
