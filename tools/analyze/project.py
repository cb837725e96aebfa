"""Whole-program model of one Python package for dmlc-analyze.

``dmlc-lint`` (tools/lint) is deliberately file-local; the rules here need
the opposite: a project-wide symbol table and call graph so a lock taken in
``scheduler/jobs.py`` can be followed into a blocking wait three modules
away. This module owns everything rule-independent:

- **Symbol table** — every module, class, method, and module function in
  the package, parsed once (pure AST; nothing is imported or executed).
- **Attribute typing** — ``self.x`` receivers are resolved to project
  classes from (in priority order) direct construction
  (``self._engine = InferenceEngine(...)``), annotations
  (``metrics: Counters | None``) on parameters/attributes, and a
  dependency-injection naming convention (``self.retry_policy = retry_policy``
  resolves to the unique class whose snake_case name is/ends with the
  attribute). Unresolvable receivers are simply not followed — the
  analysis under-approximates, it never guesses wrong edges into the
  witness chains it prints.
- **Call graph** — ``self.m()``, ``self.attr.m()``, module functions,
  imported functions, module-global instances (``tracer.record`` via
  ``tracer = Tracer()``), and class constructions (followed into
  ``__init__``). Nested ``def``/``lambda`` bodies are never scanned from
  their enclosing function (they usually run later, on another thread or
  after a lock is released) — same convention as lint rule L1.
- **Lock model** — every ``with <expr>:`` whose context expression names a
  lock (tools/lint L1's heuristic: final name contains "lock", condition
  variables exempt), identified class-qualified (``pkg.mod.Cls._lock``) so
  two instances of one class share a lock *identity* (lock-ORDER analysis
  wants exactly that: the hierarchy is per class, not per instance), plus
  whether the lock is reentrant (``threading.RLock``).
- **RPC method tables** — handler functions registered in dict literals
  returned by ``methods()`` functions or passed to ``traced_methods``;
  these are rule A3's entry points.

The model runs on arbitrary package roots, which is how the test fixtures
work: a synthetic package in tmp_path analyzes exactly like ``dmlc_tpu``.
"""

from __future__ import annotations

import ast
import functools
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

from tools.lint.rules import ImportMap, dotted_name
from tools.lint.rules.jit import _decorator_is_jit, _is_jit_name
from tools.lint.rules.locks import _lock_name as lock_display_name

MAX_DEPTH = 16  # call-graph traversal bound (protects against pathological fan-out)


@functools.lru_cache(maxsize=None)  # _unique_class_by_snake asks for every class name, per attribute
def snake_case(name: str) -> str:
    out: list[str] = []
    for i, ch in enumerate(name):
        if ch.isupper() and i and not name[i - 1].isupper():
            out.append("_")
        out.append(ch.lower())
    return "".join(out)


@dataclass
class FuncDef:
    qname: str                  # pkg.mod.Cls.meth / pkg.mod.func
    module: "ModuleInfo"
    cls: "ClassInfo | None"
    name: str
    node: ast.FunctionDef | ast.AsyncFunctionDef
    local_env: dict | None = None   # lazily-computed local var -> class qname


@dataclass
class ClassInfo:
    name: str
    qname: str
    module: "ModuleInfo"
    node: ast.ClassDef
    methods: dict[str, FuncDef] = field(default_factory=dict)
    base_names: list[str] = field(default_factory=list)   # resolved dotted
    attr_types: dict[str, str] = field(default_factory=dict)   # attr -> class qname
    lock_attrs: dict[str, bool] = field(default_factory=dict)  # attr -> reentrant


@dataclass
class ModuleInfo:
    name: str                   # dotted, e.g. dmlc_tpu.cluster.rpc
    relpath: str                # forward-slash path used in findings
    src: str
    tree: ast.Module
    imports: ImportMap
    functions: dict[str, FuncDef] = field(default_factory=dict)
    classes: dict[str, ClassInfo] = field(default_factory=dict)
    global_instances: dict[str, str] = field(default_factory=dict)  # var -> class qname
    global_locks: dict[str, bool] = field(default_factory=dict)     # var -> reentrant


@dataclass(frozen=True)
class Step:
    """One call edge in a witness chain."""

    relpath: str
    line: int
    desc: str           # "Cls.meth()" as written at the call site
    self_call: bool     # self.<m>() into the same class (lint L1's territory)

    def render(self) -> str:
        return f"{self.relpath}:{self.line}: -> {self.desc}"


@dataclass(frozen=True)
class LockSite:
    func: FuncDef
    lock_id: str        # class- or module-qualified identity
    display: str        # source spelling ("self._lock")
    line: int
    reentrant: bool
    body: tuple         # the with-statement body (ast statements)


_LOCK_CTORS = {
    "threading.Lock": False,
    "threading.RLock": True,
    "threading.Condition": True,   # cv names are excluded anyway; be safe
}


def child_nodes(node: ast.AST) -> list:
    """``ast.iter_child_nodes`` as a list kept on the node: the rules walk
    the same function bodies thousands of times and the tree never changes
    after parsing, so the generic field scan is paid once per node."""
    try:
        return node._dmlc_children  # type: ignore[attr-defined]
    except AttributeError:
        kids = node._dmlc_children = list(ast.iter_child_nodes(node))  # type: ignore[attr-defined]
        return kids


def walk(node: ast.AST):
    """``ast.walk`` over ``child_nodes``: every node under ``node``, itself
    included, breadth first as ``ast.walk`` yields them."""
    todo = deque([node])
    while todo:
        node = todo.popleft()
        todo.extend(child_nodes(node))
        yield node


def iter_calls(stmts):
    """Every ast.Call under ``stmts`` without descending into nested
    function/lambda bodies (they run later — L1's convention)."""
    stack = list(stmts)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(child_nodes(node))


def iter_withs(stmts):
    """Every ast.With under ``stmts``, same nested-def exclusion."""
    stack = list(stmts)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.With):
            yield node
        stack.extend(child_nodes(node))


class Project:
    """The parsed package. ``Project.load(package_dir)`` is the entry."""

    def __init__(self, package_name: str):
        self.package = package_name
        self.modules: dict[str, ModuleInfo] = {}
        self.classes: dict[str, ClassInfo] = {}
        self.errors: list[tuple[str, int, str]] = []  # (relpath, line, msg)
        #: resolve_call memo — rules walk overlapping closures, so the same
        #: call site is resolved many times; the AST (and hence id(call))
        #: is stable for the project's lifetime
        self._call_memo: dict[tuple[int, str], tuple["FuncDef | None", bool]] = {}

    # ---- construction ---------------------------------------------------

    @classmethod
    def load(cls, package_dir: str | Path) -> "Project":
        root = Path(package_dir)
        project = cls(root.name)
        base = root.parent
        files = sorted(
            f for f in root.rglob("*.py")
            if not any(p.startswith(".") or p == "__pycache__" for p in f.parts)
        )
        for f in files:
            rel = f.relative_to(base).as_posix()
            parts = list(f.relative_to(base).with_suffix("").parts)
            if parts[-1] == "__init__":
                parts = parts[:-1]
            dotted = ".".join(parts)
            src = f.read_text(encoding="utf-8")
            try:
                tree = ast.parse(src, filename=rel)
            except SyntaxError as e:
                project.errors.append((rel, e.lineno or 1, f"syntax error: {e.msg}"))
                continue
            project._index_module(dotted, rel, src, tree)
        for mod in project.modules.values():
            for ci in mod.classes.values():
                project._infer_class(ci)
        return project

    def _index_module(self, dotted: str, rel: str, src: str, tree: ast.Module) -> None:
        mod = ModuleInfo(dotted, rel, src, tree, ImportMap(tree))
        self.modules[dotted] = mod
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fd = FuncDef(f"{dotted}.{node.name}", mod, None, node.name, node)
                mod.functions[node.name] = fd
            elif isinstance(node, ast.ClassDef):
                ci = ClassInfo(node.name, f"{dotted}.{node.name}", mod, node)
                mod.classes[node.name] = ci
                self.classes[ci.qname] = ci
                for m in node.body:
                    if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        ci.methods[m.name] = FuncDef(
                            f"{ci.qname}.{m.name}", mod, ci, m.name, m
                        )
                ci.base_names = [
                    b for b in (mod.imports.resolve_node(base) for base in node.bases)
                    if b is not None
                ]
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                value = node.value
                if value is None or not isinstance(value, ast.Call):
                    continue
                callee = mod.imports.resolve_node(value.func)
                for t in targets:
                    if not isinstance(t, ast.Name):
                        continue
                    if callee in _LOCK_CTORS:
                        mod.global_locks[t.id] = _LOCK_CTORS[callee]
                    else:
                        # NAME = ClassName(...) at module scope (e.g. the
                        # process-global `tracer = Tracer()`).
                        target_cls = self._class_from_dotted(callee, mod)
                        if target_cls is not None:
                            mod.global_instances[t.id] = target_cls.qname

    # ---- class inference -------------------------------------------------

    def _infer_class(self, ci: ClassInfo) -> None:
        """Fill attr_types and lock_attrs from every ``self.X = ...`` in the
        class's own methods."""
        for method in ci.methods.values():
            annos = self._param_annotations(method)
            for node in walk(method.node):
                if isinstance(node, ast.AnnAssign) and self._is_self_attr(node.target):
                    attr = node.target.attr
                    hinted = self._class_from_annotation(node.annotation, ci.module)
                    if hinted is not None:
                        ci.attr_types.setdefault(attr, hinted.qname)
                    if node.value is not None:
                        self._infer_attr_value(ci, attr, node.value, annos)
                elif isinstance(node, ast.Assign):
                    for t in node.targets:
                        if self._is_self_attr(t):
                            self._infer_attr_value(ci, t.attr, node.value, annos)

    @staticmethod
    def _is_self_attr(node) -> bool:
        return (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        )

    def _infer_attr_value(self, ci: ClassInfo, attr: str, value, annos: dict) -> None:
        mod = ci.module
        if isinstance(value, ast.IfExp):
            # `self.metrics = metrics if metrics is not None else Counters()`
            self._infer_attr_value(ci, attr, value.body, annos)
            self._infer_attr_value(ci, attr, value.orelse, annos)
            return
        if isinstance(value, ast.BoolOp):
            # `self.timer = timer or time.perf_counter`
            for v in value.values:
                self._infer_attr_value(ci, attr, v, annos)
            return
        if isinstance(value, ast.Call):
            callee = mod.imports.resolve_node(value.func)
            if callee in _LOCK_CTORS:
                ci.lock_attrs.setdefault(attr, _LOCK_CTORS[callee])
                return
            target = self._class_from_dotted(callee, mod)
            if target is not None:
                ci.attr_types.setdefault(attr, target.qname)
            return
        if isinstance(value, ast.Name):
            hinted = annos.get(value.id)
            if hinted is not None:
                ci.attr_types.setdefault(attr, hinted.qname)
            elif value.id == attr:
                # Dependency injection by convention: self.retry_policy =
                # retry_policy resolves iff exactly one project class
                # snake_cases to (or ends with _) the attribute name.
                guessed = self._unique_class_by_snake(attr)
                if guessed is not None:
                    ci.attr_types.setdefault(attr, guessed.qname)

    def _param_annotations(self, fd: FuncDef) -> dict[str, ClassInfo]:
        out: dict[str, ClassInfo] = {}
        args = fd.node.args
        for a in [*args.posonlyargs, *args.args, *args.kwonlyargs]:
            if a.annotation is not None:
                hinted = self._class_from_annotation(a.annotation, fd.module)
                if hinted is not None:
                    out[a.arg] = hinted
        return out

    def _class_from_annotation(self, ann, mod: ModuleInfo) -> ClassInfo | None:
        """``Counters``, ``Counters | None``, ``Optional[Counters]`` — the
        forms the codebase uses. String annotations are not chased."""
        if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
            return (self._class_from_annotation(ann.left, mod)
                    or self._class_from_annotation(ann.right, mod))
        if isinstance(ann, ast.Subscript):
            return self._class_from_annotation(ann.slice, mod)
        if isinstance(ann, (ast.Name, ast.Attribute)):
            return self._class_from_dotted(mod.imports.resolve_node(ann), mod)
        return None

    def _class_from_dotted(self, dotted: str | None, mod: ModuleInfo) -> ClassInfo | None:
        if dotted is None:
            return None
        if dotted in mod.classes:
            return mod.classes[dotted]
        full = self.classes.get(dotted)
        if full is not None:
            return full
        # "pkg.mod.Cls" resolved through an import of the module
        owner, _, cls_name = dotted.rpartition(".")
        owner_mod = self.modules.get(owner)
        if owner_mod is not None:
            return owner_mod.classes.get(cls_name)
        return None

    def _unique_class_by_snake(self, attr: str) -> ClassInfo | None:
        hits = [
            ci for ci in self.classes.values()
            if snake_case(ci.name) == attr
            or snake_case(ci.name).endswith("_" + attr)
            or snake_case(ci.name).startswith(attr + "_")
        ]
        return hits[0] if len(hits) == 1 else None

    # ---- lookups ---------------------------------------------------------

    def lookup_method(self, ci: ClassInfo, name: str, _seen=None) -> FuncDef | None:
        if name in ci.methods:
            return ci.methods[name]
        seen = _seen or set()
        seen.add(ci.qname)
        for base in ci.base_names:
            bci = self._class_from_dotted(base, ci.module)
            if bci is not None and bci.qname not in seen:
                found = self.lookup_method(bci, name, seen)
                if found is not None:
                    return found
        return None

    def resolve_call(self, call: ast.Call, ctx: FuncDef) -> tuple[FuncDef | None, bool]:
        """The project function a call dispatches to, or None when it is
        external / dynamic / unresolvable. Second element: True when the
        edge is a ``self.m()`` call into the context function's own class
        (lint L1 already follows those)."""
        key = (id(call), ctx.qname)
        hit = self._call_memo.get(key)
        if hit is not None:
            return hit
        out = self._resolve_call(call, ctx)
        self._call_memo[key] = out
        return out

    def _resolve_call(self, call: ast.Call, ctx: FuncDef) -> tuple[FuncDef | None, bool]:
        func = call.func
        mod = ctx.module
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name) and base.id == "self" and ctx.cls is not None:
                target = self.lookup_method(ctx.cls, func.attr)
                if target is not None:
                    return target, target.cls is ctx.cls
                return None, False
            if (
                isinstance(base, ast.Attribute)
                and isinstance(base.value, ast.Name)
                and base.value.id == "self"
                and ctx.cls is not None
            ):
                cls_qname = ctx.cls.attr_types.get(base.attr)
                ci = self.classes.get(cls_qname) if cls_qname else None
                if ci is not None:
                    return self.lookup_method(ci, func.attr), False
                return None, False
            if isinstance(base, ast.Name) and base.id != "self":
                # `engine = self._ensure_engine(); engine.run_paths(...)` —
                # local variables typed by the flow-insensitive env.
                env_cls = self._local_env(ctx).get(base.id)
                if env_cls is not None:
                    ci = self.classes.get(env_cls)
                    if ci is not None:
                        return self.lookup_method(ci, func.attr), False
        dotted = mod.imports.resolve(dotted_name(func))
        if dotted is not None:
            found = self._func_from_dotted(dotted, mod)
            if found is not None:
                return found, False
        if isinstance(func, ast.Name):
            # `server = self._ensure_server(); server(batch)` -> __call__
            env_cls = self._local_env(ctx).get(func.id)
            if env_cls is not None:
                ci = self.classes.get(env_cls)
                if ci is not None:
                    return self.lookup_method(ci, "__call__"), False
        return None, False

    # ---- light type inference (locals + getter returns) ------------------

    def _local_env(self, fd: FuncDef) -> dict:
        """Flow-insensitive local-variable typing: ``x = ClassName(...)``,
        ``x = self.attr`` (typed attribute), ``x = self.m()`` where ``m`` is
        a getter whose returns all carry one project class. First binding
        wins; only ever ADDS resolvable edges (never changes existing ones).
        """
        if fd.local_env is None:
            env: dict[str, str] = {}
            for node in walk(fd.node):
                if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                cls = self._expr_class(node.value, fd)
                if cls is not None:
                    env.setdefault(node.targets[0].id, cls)
            fd.local_env = env
        return fd.local_env

    def _expr_class(self, value, fd: FuncDef) -> str | None:
        if isinstance(value, ast.Attribute) and self._is_self_attr(value) and fd.cls:
            return fd.cls.attr_types.get(value.attr)
        if not isinstance(value, ast.Call):
            return None
        func = value.func
        if self._is_self_attr(func) and fd.cls is not None:
            target = self.lookup_method(fd.cls, func.attr)
            if target is not None:
                return self._return_class(target)
            return None
        ci = self._class_from_dotted(
            fd.module.imports.resolve(dotted_name(func)), fd.module
        )
        return ci.qname if ci is not None else None

    def _return_class(self, fd: FuncDef, _seen: set | None = None) -> str | None:
        """The one project class every ``return`` of ``fd`` yields, if any —
        the lazy-getter pattern (``_ensure_engine`` returning
        ``self._engine``)."""
        seen = _seen or set()
        if fd.qname in seen:
            return None
        seen.add(fd.qname)
        classes: set[str] = set()
        for node in walk(fd.node):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            if isinstance(node.value, ast.Attribute) and self._is_self_attr(node.value) and fd.cls:
                cls = fd.cls.attr_types.get(node.value.attr)
            elif isinstance(node.value, ast.Call) and self._is_self_attr(node.value.func) and fd.cls:
                target = self.lookup_method(fd.cls, node.value.func.attr)
                cls = self._return_class(target, seen) if target else None
            else:
                cls = None
            if cls is None:
                return None
            classes.add(cls)
        return classes.pop() if len(classes) == 1 else None

    def _func_from_dotted(self, dotted: str, mod: ModuleInfo) -> FuncDef | None:
        head, _, last = dotted.rpartition(".")
        if not head:
            # bare local name: module function or local class construction
            if dotted in mod.functions:
                return mod.functions[dotted]
            ci = mod.classes.get(dotted)
            return ci.methods.get("__init__") if ci is not None else None
        # longest-prefix module match
        parts = dotted.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            owner = self.modules.get(".".join(parts[:cut]))
            if owner is None:
                continue
            rest = parts[cut:]
            if len(rest) == 1:
                fd = owner.functions.get(rest[0])
                if fd is not None:
                    return fd
                ci = owner.classes.get(rest[0])
                return ci.methods.get("__init__") if ci is not None else None
            if len(rest) == 2:
                obj, meth = rest
                ci = owner.classes.get(obj)
                if ci is None:
                    inst = owner.global_instances.get(obj)
                    ci = self.classes.get(inst) if inst else None
                if ci is not None:
                    return self.lookup_method(ci, meth)
            return None
        # "Cls.meth" / "instance.meth" where Cls was from-imported
        owner_cls = self._class_from_dotted(head, mod)
        if owner_cls is not None:
            return self.lookup_method(owner_cls, last)
        inst_cls = mod.global_instances.get(head)
        if inst_cls is not None:
            ci = self.classes.get(inst_cls)
            if ci is not None:
                return self.lookup_method(ci, last)
        return None

    # ---- lock model ------------------------------------------------------

    def lock_sites(self) -> list[LockSite]:
        out: list[LockSite] = []
        for mod in self.modules.values():
            for fd in self._all_funcs(mod):
                for node in iter_withs(fd.node.body):
                    for item in node.items:
                        display = lock_display_name(item.context_expr)
                        if display is None:
                            continue
                        lock_id, reentrant = self._lock_identity(display, fd)
                        out.append(LockSite(
                            fd, lock_id, display, node.lineno, reentrant,
                            tuple(node.body),
                        ))
        return out

    def _all_funcs(self, mod: ModuleInfo):
        yield from mod.functions.values()
        for ci in mod.classes.values():
            yield from ci.methods.values()

    def _lock_identity(self, display: str, fd: FuncDef) -> tuple[str, bool]:
        parts = display.split(".")
        if parts[0] == "self" and fd.cls is not None:
            attr = parts[-1]
            owner = fd.cls
            reentrant = owner.lock_attrs.get(attr)
            if reentrant is None:  # inherited lock attr
                for base in owner.base_names:
                    bci = self._class_from_dotted(base, fd.module)
                    if bci is not None and attr in bci.lock_attrs:
                        owner, reentrant = bci, bci.lock_attrs[attr]
                        break
            return f"{owner.qname}.{attr}", bool(reentrant)
        if len(parts) == 1:
            reentrant = fd.module.global_locks.get(parts[0], False)
            return f"{fd.module.name}.{parts[0]}", reentrant
        # e.g. ``with other.lock:`` — identity by spelling, module-scoped
        return f"{fd.module.name}.{display}", False

    # ---- interprocedural traversal --------------------------------------

    def reachable_contexts(self, start: FuncDef, stmts, max_depth: int = MAX_DEPTH):
        """BFS from ``stmts`` (executed inside ``start``) through resolvable
        project calls. Yields ``(func, stmts, chain)``: the context function,
        the statements that execute in the source context (for ``start`` the
        given statements; for callees their whole body), and the chain of
        Steps taken to get there. Each function is visited once — the first
        (shortest) chain wins, which is also the best witness."""
        yield start, stmts, ()
        seen = {start.qname}
        frontier: list[tuple[FuncDef, tuple, tuple]] = [(start, tuple(stmts), ())]
        depth = 0
        while frontier and depth < max_depth:
            depth += 1
            nxt: list[tuple[FuncDef, tuple, tuple]] = []
            for ctx, ctx_stmts, chain in frontier:
                for call in iter_calls(ctx_stmts):
                    callee, is_self = self.resolve_call(call, ctx)
                    if callee is None or callee.qname in seen:
                        continue
                    seen.add(callee.qname)
                    desc = dotted_name(call.func) or getattr(call.func, "attr", "?")
                    label = callee.qname[len(self.package) + 1:]
                    step = Step(
                        ctx.module.relpath, call.lineno,
                        f"{desc}()  [{label}]", is_self,
                    )
                    new_chain = chain + (step,)
                    yield callee, tuple(callee.node.body), new_chain
                    nxt.append((callee, tuple(callee.node.body), new_chain))
            frontier = nxt

    # ---- RPC method tables ----------------------------------------------

    def rpc_handlers(self) -> list[tuple[str, FuncDef, str, int]]:
        """(method_name, handler, relpath, line) for every handler found in
        a dict literal that is (a) inside a function named ``methods`` or
        (b) an argument to a ``traced_methods(...)`` call. Lambdas and
        unresolvable values are skipped."""
        out: list[tuple[str, FuncDef, str, int]] = []
        for mod in self.modules.values():
            for fd in self._all_funcs(mod):
                in_methods_fn = fd.name == "methods"
                for node in walk(fd.node):
                    if isinstance(node, ast.Call):
                        callee = mod.imports.resolve(dotted_name(node.func))
                        is_tm = callee is not None and callee.split(".")[-1] == "traced_methods"
                        if not is_tm:
                            continue
                        dicts = [a for a in node.args if isinstance(a, ast.Dict)]
                    elif in_methods_fn and isinstance(node, ast.Return) and isinstance(node.value, ast.Dict):
                        dicts = [node.value]
                    else:
                        continue
                    for d in dicts:
                        for k, v in zip(d.keys, d.values):
                            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                                continue
                            handler = self._handler_target(v, fd)
                            if handler is not None:
                                out.append((k.value, handler, mod.relpath, v.lineno))
        return out

    def _handler_target(self, value, ctx: FuncDef) -> FuncDef | None:
        if isinstance(value, ast.Attribute) and self._is_self_attr(value) and ctx.cls:
            return self.lookup_method(ctx.cls, value.attr)
        if isinstance(value, (ast.Name, ast.Attribute)):
            dotted = ctx.module.imports.resolve(dotted_name(value))
            if dotted is not None:
                return self._func_from_dotted(dotted, ctx.module)
        return None

    # ---- device-semantics model (rules A5-A8) ---------------------------

    def device_model(self) -> "DeviceModel":
        """The jit-wrapper / mesh-axis view of the project, built once per
        run and shared by the A5-A8 rule family (docs/ANALYZE.md)."""
        if getattr(self, "_device_model", None) is None:
            self._device_model = DeviceModel(self)
        return self._device_model


# ---- device semantics: jit wrappers, mesh axes, hot entry points ---------
#
# Everything below models what the XLA runtime will *actually do* with the
# code — which buffers a compiled program is allowed to invalidate
# (donate_argnums), which call-site argument shapes key its compilation
# cache, and which mesh axes a PartitionSpec or collective may legally
# name. The same under-approximation contract as the rest of this module
# applies: an edge/axis/donation is recorded only when it is statically
# certain, so rule findings are real program behaviors, never guesses.

_ARRAY_CTORS = {
    "zeros", "ones", "full", "empty", "arange", "asarray", "array",
    "broadcast_to", "linspace",
}

_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "axis_index", "all_gather",
    "all_to_all", "ppermute",
}


@dataclass
class JitWrapper:
    """One compiled program: a function wrapped by jax.jit/pjit, however
    the binding was spelled (decorator, local ``w = jax.jit(f)``, attribute
    ``self._step = jax.jit(step)`` — including the builder-method idiom
    ``self._step = self._build_step()`` whose builder returns the jit)."""

    kind: str                       # "decorated" | "local" | "attr"
    name: str                       # callable spelling at call sites
    relpath: str
    line: int                       # jit construction (or decorator) line
    fn_node: ast.FunctionDef | ast.AsyncFunctionDef | None
    owner: FuncDef | None           # function holding a local binding
    cls_qname: str | None           # class owning an attr binding
    target_fd: FuncDef | None       # project FuncDef when the wrapped fn has one
    donate: set[int] = field(default_factory=set)
    static: set[int] = field(default_factory=set)
    static_names: set[str] = field(default_factory=set)

    @property
    def param_names(self) -> tuple[str, ...]:
        if self.fn_node is None:
            return ()
        a = self.fn_node.args
        return tuple(p.arg for p in [*a.posonlyargs, *a.args])

    def self_offset(self, call: ast.Call) -> int:
        """Positional-arg offset between call-site args and wrapped params
        (1 for a jit-decorated method invoked as ``self.m(...)``)."""
        if (self.target_fd is not None and self.target_fd.cls is not None
                and self.kind == "decorated"):
            f = call.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                    and f.value.id == "self":
                return 1
        return 0


@dataclass(frozen=True)
class MeshDef:
    axes: tuple[str, ...]
    relpath: str
    line: int


def _literal_str_tuple(node) -> tuple[str, ...] | None:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.append(e.value)
        return tuple(out)
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    return None


def _literal_int_set(node) -> set[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[int] = set()
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                return set()
            out.add(e.value)
        return out
    return set()


def _own_returns(fn_node) -> list[ast.Return]:
    """``return <expr>`` statements of ``fn_node`` ITSELF — a builder whose
    jitted target is a nested def must not count the target's returns."""
    out: list[ast.Return] = []
    stack = list(fn_node.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        if isinstance(node, ast.Return):
            if node.value is not None:
                out.append(node)
            continue
        stack.extend(child_nodes(node))
    return out


def nested_defs(fn_node) -> dict[str, ast.FunctionDef]:
    """Name -> def for functions nested (at any depth) inside ``fn_node``."""
    out: dict[str, ast.FunctionDef] = {}
    for node in walk(fn_node):
        if node is fn_node:
            continue
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, node)
    return out


class DeviceModel:
    """Jit wrappers + call sites, mesh axis environments, shard_map sites,
    and hot-path entry points for one loaded Project."""

    def __init__(self, project: Project):
        self.project = project
        self.wrappers: list[JitWrapper] = []
        #: (owner_qname, name) -> wrapper, for `w = jax.jit(f)` locals
        self._local: dict[tuple[str, str], JitWrapper] = {}
        #: (cls_qname, attr) -> wrapper, for `self.X = jax.jit(f)` attrs
        self._attr: dict[tuple[str, str], JitWrapper] = {}
        #: FuncDef qname -> wrapper, for decorated functions/methods
        self._decorated: dict[str, JitWrapper] = {}
        #: module var -> MeshDef with statically-known axis names
        self.module_meshes: dict[tuple[str, str], MeshDef] = {}
        #: (cls_qname, attr) -> MeshDef
        self.attr_meshes: dict[tuple[str, str], MeshDef] = {}
        self._build()
        self._sites: dict[int, list[tuple[FuncDef, ast.Call]]] | None = None

    # -- construction ------------------------------------------------------

    def _build(self) -> None:
        for mod in self.project.modules.values():
            self._scan_module_meshes(mod)
            for fd in self.project._all_funcs(mod):
                self._scan_decorated(fd)
                self._scan_bindings(fd)

    def _scan_module_meshes(self, mod: ModuleInfo) -> None:
        for node in mod.tree.body:
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            md = self.mesh_from_expr(node.value, mod, None)
            if md is not None:
                self.module_meshes[(mod.name, node.targets[0].id)] = md

    def _scan_decorated(self, fd: FuncDef) -> None:
        imports = fd.module.imports
        for dec in fd.node.decorator_list:
            if not _decorator_is_jit(dec, imports):
                continue
            w = JitWrapper(
                "decorated", fd.name, fd.module.relpath, dec.lineno,
                fd.node, None, fd.cls.qname if fd.cls else None, fd,
            )
            self._jit_kwargs(dec if isinstance(dec, ast.Call) else None, w)
            self.wrappers.append(w)
            self._decorated[fd.qname] = w
            return

    def _scan_bindings(self, fd: FuncDef) -> None:
        """``w = jax.jit(f, ...)`` locals, ``self.X = jax.jit(f, ...)``
        attrs, and the builder idiom ``self.X = self._build()`` where the
        builder's single return is a jit call. Also nested defs decorated
        with jit (they behave as local bindings of their own name)."""
        imports = fd.module.imports
        local_defs = nested_defs(fd.node)
        for name, node in local_defs.items():
            if any(_decorator_is_jit(d, imports) for d in node.decorator_list):
                w = JitWrapper("local", name, fd.module.relpath,
                               node.decorator_list[0].lineno, node, fd, None, None)
                dec = next(d for d in node.decorator_list
                           if _decorator_is_jit(d, imports))
                self._jit_kwargs(dec if isinstance(dec, ast.Call) else None, w)
                self.wrappers.append(w)
                self._local[(fd.qname, name)] = w
        for node in walk(fd.node):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target, value = node.targets[0], node.value
            jit_call = self._as_jit_call(value, fd, local_defs)
            if isinstance(target, ast.Name):
                if jit_call is not None:
                    w = self._wrapper_from_jit_call(
                        jit_call, "local", target.id, fd, local_defs)
                    self._register_local(fd, target.id, w)
                continue
            if not Project._is_self_attr(target) or fd.cls is None:
                continue
            attr = target.attr
            if jit_call is not None:
                w = self._wrapper_from_jit_call(
                    jit_call, "attr", f"self.{attr}", fd, local_defs)
                self._register_attr(fd.cls, attr, w)
                continue
            md = self.mesh_from_expr(value, fd.module, fd)
            if md is not None:
                self.attr_meshes.setdefault((fd.cls.qname, attr), md)

    def _as_jit_call(self, value, fd: FuncDef, local_defs) -> ast.Call | None:
        """``value`` as a jit(...) construction: either directly, or a call
        to a same-class builder method whose only return is one."""
        if not isinstance(value, ast.Call):
            return None
        if _is_jit_name(value.func, fd.module.imports):
            return value
        if Project._is_self_attr(value.func) and fd.cls is not None:
            builder = self.project.lookup_method(fd.cls, value.func.attr)
            if builder is None:
                return None
            returns = _own_returns(builder.node)
            if len(returns) == 1 and isinstance(returns[0].value, ast.Call) \
                    and _is_jit_name(returns[0].value.func, builder.module.imports):
                # Remember the builder so the wrapped nested def resolves in
                # the builder's scope, not the assigning method's.
                self._builder_ctx = builder
                return returns[0].value
        return None

    def _wrapper_from_jit_call(self, call: ast.Call, kind: str, name: str,
                               fd: FuncDef, local_defs) -> JitWrapper:
        builder = getattr(self, "_builder_ctx", None)
        self._builder_ctx = None
        scope_fd = builder or fd
        scope_defs = nested_defs(scope_fd.node) if builder else local_defs
        fn_node, target_fd = None, None
        if call.args and isinstance(call.args[0], ast.Name):
            wrapped = call.args[0].id
            fn_node = scope_defs.get(wrapped)
            if fn_node is None:
                dotted = scope_fd.module.imports.resolve(wrapped)
                target_fd = (self.project._func_from_dotted(dotted, scope_fd.module)
                             if dotted else None)
                fn_node = target_fd.node if target_fd is not None else None
        w = JitWrapper(
            kind, name, scope_fd.module.relpath, call.lineno, fn_node,
            fd if kind == "local" else None,
            fd.cls.qname if (kind == "attr" and fd.cls) else None, target_fd,
        )
        self._jit_kwargs(call, w)
        return w

    def _register_local(self, fd: FuncDef, name: str, w: JitWrapper) -> None:
        prev = self._local.get((fd.qname, name))
        if prev is None:
            self._local[(fd.qname, name)] = w
            self.wrappers.append(w)
        else:
            prev.donate |= w.donate
            prev.static |= w.static
            prev.static_names |= w.static_names

    def _register_attr(self, cls: ClassInfo, attr: str, w: JitWrapper) -> None:
        """Several bindings of one attr (platform branches) merge: donation
        holds on SOME real path, which is what A5 reports against."""
        prev = self._attr.get((cls.qname, attr))
        if prev is None:
            self._attr[(cls.qname, attr)] = w
            self.wrappers.append(w)
        else:
            prev.donate |= w.donate
            prev.static |= w.static
            prev.static_names |= w.static_names

    def _jit_kwargs(self, call: ast.Call | None, w: JitWrapper) -> None:
        if call is None:
            return
        names: dict[str, ast.expr] = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        w.donate |= _literal_int_set(names.get("donate_argnums"))
        w.static |= _literal_int_set(names.get("static_argnums"))
        w.static_names |= set(_literal_str_tuple(names.get("static_argnames")) or ())
        donate_names = _literal_str_tuple(names.get("donate_argnames")) or ()
        params = w.param_names
        for n in donate_names:
            if n in params:
                w.donate.add(params.index(n))
        for n in tuple(w.static_names):
            if n in params:
                w.static.add(params.index(n))

    # -- call sites --------------------------------------------------------

    def call_sites(self, w: JitWrapper) -> list[tuple[FuncDef, ast.Call]]:
        if self._sites is None:
            self._sites = {id(x): [] for x in self.wrappers}
            for mod in self.project.modules.values():
                for fd in self.project._all_funcs(mod):
                    for call in iter_calls(fd.node.body):
                        hit = self.wrapper_for_call(call, fd)
                        if hit is not None:
                            self._sites[id(hit)].append((fd, call))
        return self._sites.get(id(w), [])

    def wrapper_for_call(self, call: ast.Call, ctx: FuncDef) -> JitWrapper | None:
        func = call.func
        if isinstance(func, ast.Name):
            local = self._local.get((ctx.qname, func.id))
            if local is not None:
                return local
            callee, _ = self.project.resolve_call(call, ctx)
            if callee is not None:
                return self._decorated.get(callee.qname)
            return None
        if Project._is_self_attr(func) and ctx.cls is not None:
            hit = self._attr.get((ctx.cls.qname, func.attr))
            if hit is not None:
                return hit
            callee, _ = self.project.resolve_call(call, ctx)
            if callee is not None:
                return self._decorated.get(callee.qname)
        return None

    # -- hot entry points (rule A7) ---------------------------------------

    def hot_funcs(self) -> list[FuncDef]:
        out = []
        for mod in self.project.modules.values():
            for fd in self.project._all_funcs(mod):
                if fd.name.endswith("_hot"):
                    out.append(fd)
                    continue
                for dec in fd.node.decorator_list:
                    node = dec.func if isinstance(dec, ast.Call) else dec
                    name = mod.imports.resolve_node(node) or ""
                    if name.rsplit(".", 1)[-1] == "hot_path":
                        out.append(fd)
                        break
        return out

    def jit_body_lines(self, relpath: str) -> set[int]:
        """Line numbers inside jit-wrapped function bodies of one file —
        A7's precedence boundary with lint J1 (which owns syncs there)."""
        out: set[int] = set()
        for w in self.wrappers:
            if w.relpath != relpath or w.fn_node is None:
                continue
            end = getattr(w.fn_node, "end_lineno", None)
            if end is not None:
                out.update(range(w.fn_node.lineno, end + 1))
        return out

    # -- mesh axis environments (rule A8) ---------------------------------

    def mesh_from_expr(self, value, mod: ModuleInfo, fd: FuncDef | None) -> MeshDef | None:
        """Statically-known axis names of a mesh-constructing expression:
        ``Mesh(grid, axis_names=(...literals...))`` or
        ``make_mesh({'dp': ..., ...})`` (dict-literal keys; no-arg form is
        the documented all-devices single ``dp`` axis)."""
        if not isinstance(value, ast.Call):
            return None
        dotted = mod.imports.resolve_node(value.func) or ""
        last = dotted.rsplit(".", 1)[-1]
        if last == "Mesh":
            cand = next((kw.value for kw in value.keywords
                         if kw.arg == "axis_names"), None)
            if cand is None and len(value.args) >= 2:
                cand = value.args[1]
            axes = _literal_str_tuple(cand) if cand is not None else None
            if axes:
                return MeshDef(axes, mod.relpath, value.lineno)
            return None
        if last == "make_mesh":
            if not value.args and not any(kw.arg == "axes" for kw in value.keywords):
                return MeshDef(("dp",), mod.relpath, value.lineno)
            cand = value.args[0] if value.args else next(
                (kw.value for kw in value.keywords if kw.arg == "axes"), None)
            if isinstance(cand, ast.Dict):
                keys = []
                for k in cand.keys:
                    if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                        return None
                    keys.append(k.value)
                return MeshDef(tuple(keys), mod.relpath, value.lineno)
            if cand is not None:
                # jax.make_mesh(shape, axis_names) positional form
                axes = _literal_str_tuple(value.args[1]) if len(value.args) >= 2 else None
                if axes:
                    return MeshDef(axes, mod.relpath, value.lineno)
        return None

    def resolve_mesh(self, expr, ctx: FuncDef) -> MeshDef | None:
        """Axis names for a mesh expression at a use site: direct
        construction, a local bound to one, ``self.X`` bound to one, or a
        module-global mesh. Anything else (mesh passed as a parameter) is
        unknown and keeps A8 silent — the under-approximation contract."""
        md = self.mesh_from_expr(expr, ctx.module, ctx)
        if md is not None:
            return md
        if isinstance(expr, ast.Name):
            for node in walk(ctx.node):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == expr.id):
                    md = self.mesh_from_expr(node.value, ctx.module, ctx)
                    if md is not None:
                        return md
            return self.module_meshes.get((ctx.module.name, expr.id))
        if Project._is_self_attr(expr) and ctx.cls is not None:
            return self.attr_meshes.get((ctx.cls.qname, expr.attr))
        return None
