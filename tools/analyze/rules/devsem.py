"""A5-A8 — device semantics: donation, recompile census, host sync, mesh axes.

dmlc-lint's J-rules are lexical: they see one file and flag what is wrong
*inside* a jit context. These four rules run on the whole-program model
(``project.DeviceModel``) instead, because the hazards they cover live in
the space BETWEEN the jit construction and its call sites:

- **A5** a buffer passed at a ``donate_argnums``/``donate_argnames``
  position is invalid the moment the call dispatches — XLA may alias its
  HBM for the outputs. Reading it afterwards (on any real path, including
  the loop back-edge that re-donates it, or three ``self.m()`` hops away)
  is a runtime error on hardware and silent garbage on some backends.
- **A6** one jitted program, many call-site signatures: every distinct
  abstract signature is a separate XLA compilation (the 22 s first-hit
  problem). The rule takes a census of per-call-site signature
  descriptors and flags programs whose family is unbounded (shape derived
  from a loop variable or ``len(arg)``) or larger than K, plus unhashable
  static arguments and traced parameters that drive Python control flow.
- **A7** J1 made interprocedural: a host sync (``.item()``,
  ``block_until_ready``, ``jax.device_get``, ``float()``/``np.asarray``
  on a jit result, control flow on an indexed jit result) reached from a
  ``@hot_path``/``*_hot`` function through the call graph stalls the
  serving pipeline from code the hot function cannot see.
- **A8** axis names in ``shard_map`` specs, ``PartitionSpec``/
  ``NamedSharding`` and collectives (``psum``/``pmean``/``axis_index``…)
  must be declared by the statically-known enclosing mesh; spec rank must
  not exceed derivable operand rank; ``in_specs`` arity must match the
  immediate call's operand count. Partition-rule tables (literal
  ``(regex, PartitionSpec)`` sequences, parallel/sharding.py grammar) must
  have compiling regexes, no rule dead behind a catch-all or duplicate
  (first match wins), and a terminal catch-all — without one, params
  matched by no rule are spec-less at mesh>1.

Precedence with lint (one finding never fires twice): J1 owns host syncs
*inside* jit-wrapped functions in its scope (parallel/, ops/) — A7 skips
those lines. J2 owns jit-in-loop construction; A6 only looks at call
sites of recognized wrappers. J3 owns missing donation on train steps;
A5 only fires where donation IS present. All four under-approximate: a
finding is emitted only when the behavior is statically certain, so a
clean run means "nothing provable", and every witness chain is a real
path (docs/ANALYZE.md).
"""

from __future__ import annotations

import ast

from tools.analyze.core import Analysis, Finding
from tools.analyze.project import (
    walk,
    FuncDef,
    JitWrapper,
    Project,
    Step,
    iter_calls,
    nested_defs,
)
from tools.lint.rules import dotted_name

_INTERPROC_DEPTH = 6


# ---- shared AST plumbing -------------------------------------------------

def _contains(stmt, target) -> bool:
    return any(n is target for n in walk(stmt))


def _sub_bodies(stmt):
    for name in ("body", "orelse", "finalbody"):
        sub = getattr(stmt, name, None)
        if isinstance(sub, list) and sub and isinstance(sub[0], ast.stmt):
            yield sub
    for h in getattr(stmt, "handlers", ()):
        yield h.body


def _stmt_path(body, target):
    """[(stmts, idx, stmt), ...] outer->inner such that each level's stmt
    contains ``target``; the innermost stmt is the one holding it."""
    for i, stmt in enumerate(body):
        if not _contains(stmt, target):
            continue
        level = [(body, i, stmt)]
        for sub in _sub_bodies(stmt):
            rest = _stmt_path(sub, target)
            if rest:
                return level + rest
        return level
    return []


# ---- A5: donation dataflow ----------------------------------------------
#
# A "location" is ("name", ident) for a local, or ("attr", attr, cls_qname)
# for a self attribute. The scan walks statements in execution order from
# the donating call: the first certain access decides — a Store kills the
# taint, a Load is the finding. Branch semantics are deliberately
# asymmetric (the under-approximation contract): a Load in EITHER branch
# is a real path and flags, but a Store only kills when EVERY branch
# stores; stores inside loops never kill (the zero-iteration path skips
# them). Calls are followed into same-class methods for attr locations
# (same instance, statically certain), building the witness chain.

_KILL = ("kill",)


def _targets_kill(targets, loc) -> bool:
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            if _targets_kill(t.elts, loc):
                return True
        elif isinstance(t, ast.Starred):
            if _targets_kill([t.value], loc):
                return True
        elif loc[0] == "name" and isinstance(t, ast.Name) and t.id == loc[1]:
            return True
        elif (loc[0] == "attr" and isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name) and t.value.id == "self"
                and t.attr == loc[1]):
            return True
    return False


class _DonationScan:
    def __init__(self, project: Project, loc):
        self.project = project
        self.loc = loc
        self.seen: set[str] = set()

    # -- expressions -------------------------------------------------------

    def expr(self, node, ctx: FuncDef, depth: int, chain: tuple):
        """First access inside an expression subtree, in field order."""
        if node is None or isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            return None  # nested defs run later (L1's convention)
        loc = self.loc
        if isinstance(node, ast.Name) and loc[0] == "name" and node.id == loc[1]:
            return ("load", ctx.module.relpath, node.lineno,
                    f"reads {loc[1]!r}", chain)
        if (isinstance(node, ast.Attribute) and loc[0] == "attr"
                and isinstance(node.value, ast.Name) and node.value.id == "self"
                and node.attr == loc[1]):
            return ("load", ctx.module.relpath, node.lineno,
                    f"reads self.{loc[1]}", chain)
        if isinstance(node, ast.Call):
            r = self._call(node, ctx, depth, chain)
            if r is not None:
                return r
            return None
        for child in ast.iter_child_nodes(node):
            r = self.expr(child, ctx, depth, chain)
            if r is not None:
                return r
        return None

    def _call(self, call: ast.Call, ctx: FuncDef, depth: int, chain: tuple):
        # args/func evaluate first: a direct mention of the location in the
        # call expression is an ordinary Load.
        for child in ast.iter_child_nodes(call):
            r = self.expr(child, ctx, depth, chain)
            if r is not None:
                return r
        # Then the callee body runs: follow same-class methods for attr
        # locations (provably the same instance's attribute).
        if self.loc[0] != "attr" or depth <= 0:
            return None
        callee, _ = self.project.resolve_call(call, ctx)
        if callee is None or callee.cls is None or callee.cls.qname != self.loc[2]:
            return None
        if callee.qname in self.seen:
            return None
        self.seen.add(callee.qname)
        desc = dotted_name(call.func) or getattr(call.func, "attr", "?")
        label = callee.qname[len(self.project.package) + 1:]
        step = Step(ctx.module.relpath, call.lineno, f"{desc}()  [{label}]",
                    callee.cls is ctx.cls)
        r = self.stmts(callee.node.body, callee, depth - 1, chain + (step,))
        return r  # load propagates with chain; kill propagates; None falls out

    # -- statements --------------------------------------------------------

    def stmts(self, body, ctx: FuncDef, depth: int, chain: tuple):
        for stmt in body:
            r = self.stmt(stmt, ctx, depth, chain)
            if r is not None:
                return r
        return None

    def stmt(self, stmt, ctx: FuncDef, depth: int, chain: tuple):
        loc = self.loc
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return None
        if isinstance(stmt, ast.If):
            r = self.expr(stmt.test, ctx, depth, chain)
            if r is not None:
                return r
            return self._branches([stmt.body, stmt.orelse], ctx, depth, chain)
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            head = stmt.test if isinstance(stmt, ast.While) else stmt.iter
            r = self.expr(head, ctx, depth, chain)
            if r is not None:
                return r
            for sub in (stmt.body, stmt.orelse):
                r = self.stmts(sub, ctx, depth, chain)
                if r is not None and r[0] == "load":
                    return r
            return None  # loop-body stores never kill (zero-iteration path)
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                r = self.expr(item.context_expr, ctx, depth, chain)
                if r is not None:
                    return r
                if item.optional_vars is not None and _targets_kill(
                        [item.optional_vars], loc):
                    return _KILL
            return self.stmts(stmt.body, ctx, depth, chain)
        if isinstance(stmt, ast.Try):
            r = self.stmts(stmt.body, ctx, depth, chain)
            if r is not None and r[0] == "load":
                return r
            for h in stmt.handlers:
                r = self.stmts(h.body, ctx, depth, chain)
                if r is not None and r[0] == "load":
                    return r
            r = self.stmts(stmt.orelse, ctx, depth, chain)
            if r is not None and r[0] == "load":
                return r
            return self.stmts(stmt.finalbody, ctx, depth, chain)
        if isinstance(stmt, ast.Assign):
            r = self.expr(stmt.value, ctx, depth, chain)
            if r is not None:
                return r
            return _KILL if _targets_kill(stmt.targets, loc) else None
        if isinstance(stmt, ast.AnnAssign):
            r = self.expr(stmt.value, ctx, depth, chain)
            if r is not None:
                return r
            if stmt.value is not None and _targets_kill([stmt.target], loc):
                return _KILL
            return None
        if isinstance(stmt, ast.AugAssign):
            if _targets_kill([stmt.target], loc):
                return ("load", ctx.module.relpath, stmt.lineno,
                        "augmented assignment reads the old value", chain)
            r = self.expr(stmt.value, ctx, depth, chain)
            if r is not None:
                return r
            return self.expr(stmt.target, ctx, depth, chain)
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if _targets_kill([t], loc):
                    return _KILL
            return None
        if isinstance(stmt, (ast.Return, ast.Raise)):
            for child in ast.iter_child_nodes(stmt):
                r = self.expr(child, ctx, depth, chain)
                if r is not None:
                    return r
            return _KILL  # path ends without touching the location
        if isinstance(stmt, (ast.Break, ast.Continue)):
            return _KILL
        for child in ast.iter_child_nodes(stmt):
            r = self.expr(child, ctx, depth, chain)
            if r is not None:
                return r
        return None

    def _branches(self, bodies, ctx, depth, chain):
        results = [self.stmts(b, ctx, depth, chain) for b in bodies]
        for r in results:
            if r is not None and r[0] == "load":
                return r
        if bodies and all(b and r is not None for b, r in zip(bodies, results)):
            return _KILL  # every branch (and there IS an else) re-stores
        return None


def _scan_after_donation(ctx: FuncDef, call: ast.Call, loc, project: Project):
    """The first certain access to ``loc`` on any path after ``call``:
    rest of the enclosing blocks outward, plus the back-edge of every
    enclosing loop (where the next iteration re-reads — or re-donates —
    the location)."""
    path = _stmt_path(ctx.node.body, call)
    if not path:
        return None
    scan = _DonationScan(project, loc)
    donating_stmt = path[-1][2]
    if isinstance(donating_stmt, ast.Assign) and _targets_kill(
            donating_stmt.targets, loc):
        return None  # `state = step(state, ...)` — rebound by its own result
    for level in range(len(path) - 1, -1, -1):
        stmts, idx, stmt = path[level]
        r = scan.stmts(stmts[idx + 1:], ctx, _INTERPROC_DEPTH, ())
        if r is not None:
            return r if r[0] == "load" else None
        encl = path[level - 1][2] if level > 0 else None
        if isinstance(encl, (ast.For, ast.AsyncFor, ast.While)) and stmts is encl.body:
            back = None
            if isinstance(encl, ast.While):
                back = scan.expr(encl.test, ctx, _INTERPROC_DEPTH, ())
            if back is None:
                back = scan.stmts(stmts[:idx], ctx, _INTERPROC_DEPTH, ())
            if back is not None and back[0] == "load":
                return back
            if back is None:
                # Nothing on the back-edge rebinds it: the next iteration
                # re-donates an already-invalidated buffer.
                return ("load", ctx.module.relpath, call.lineno,
                        "re-donated on the next loop iteration without "
                        "rebinding", ())
            # back-edge kills; the exit path continues at the outer level
    return None


def _display(arg) -> str:
    return dotted_name(arg) or "<expr>"


class _A5:
    id = "A5"
    summary = "donated buffer read after the donating call (interprocedural)"
    hint = ("a donate_argnums buffer is invalid once the call dispatches — "
            "rebind the reference from the call's results (state = "
            "step(state, ...)), drop the donation, or justify with "
            "'# dmlc-lint: disable=A5 -- why' on the donating call line")

    def check(self, analysis: Analysis) -> None:
        dm = analysis.project.device_model()
        for w in dm.wrappers:
            if not w.donate:
                continue
            for ctx, call in dm.call_sites(w):
                off = w.self_offset(call)
                for pos in sorted(w.donate):
                    arg = self._arg_at(w, call, pos, off)
                    if arg is None:
                        continue
                    loc = self._location(arg, ctx)
                    if loc is None:
                        continue
                    r = _scan_after_donation(ctx, call, loc, analysis.project)
                    if r is None:
                        continue
                    _, relpath, line, desc, chain = r
                    witness = chain + (Step(relpath, line, desc, True),)
                    analysis.findings.append(Finding(
                        ctx.module.relpath, call.lineno, call.col_offset,
                        self.id,
                        f"{_display(arg)} is donated to jitted {w.name!r} "
                        f"(argnum {pos}) and read again afterwards",
                        witness,
                    ))

    @staticmethod
    def _arg_at(w: JitWrapper, call: ast.Call, pos: int, off: int):
        i = pos + off
        if i < len(call.args):
            a = call.args[i]
            return None if isinstance(a, ast.Starred) else a
        params = w.param_names
        if pos < len(params):
            for kw in call.keywords:
                if kw.arg == params[pos]:
                    return kw.value
        return None

    @staticmethod
    def _location(arg, ctx: FuncDef):
        if isinstance(arg, ast.Name):
            return ("name", arg.id)
        if (isinstance(arg, ast.Attribute) and isinstance(arg.value, ast.Name)
                and arg.value.id == "self" and ctx.cls is not None):
            return ("attr", arg.attr, ctx.cls.qname)
        return None


# ---- A6: signature census ------------------------------------------------

_ARRAY_CTORS = {"zeros", "ones", "full", "empty", "arange", "asarray",
                "array", "broadcast_to", "linspace"}


def _fp(node) -> str:
    """Compact, stable fingerprint of an expression for census identity."""
    if node is None:
        return "-"
    d = dotted_name(node)
    if d is not None:
        return d
    if isinstance(node, ast.Constant):
        return repr(node.value)
    if isinstance(node, (ast.Tuple, ast.List)):
        return "(" + ",".join(_fp(e) for e in node.elts) + ")"
    if isinstance(node, ast.Call):
        return _fp(node.func) + "(" + ",".join(_fp(a) for a in node.args) + ")"
    if isinstance(node, ast.Subscript):
        return _fp(node.value) + "[" + _fp(node.slice) + "]"
    if isinstance(node, ast.BinOp):
        return _fp(node.left) + type(node.op).__name__ + _fp(node.right)
    if isinstance(node, ast.UnaryOp):
        return type(node.op).__name__ + _fp(node.operand)
    if isinstance(node, ast.Attribute):
        return _fp(node.value) + "." + node.attr
    return type(node).__name__


def _loop_vars(ctx: FuncDef, call: ast.Call) -> set[str]:
    """Names rebound per-iteration by loops/comprehensions enclosing the
    call site — a signature built from one varies without bound."""
    out: set[str] = set()
    for _, _, stmt in _stmt_path(ctx.node.body, call):
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            for n in walk(stmt.target):
                if isinstance(n, ast.Name):
                    out.add(n.id)
    for node in walk(ctx.node):
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)) and _contains(node, call):
            for gen in node.generators:
                for n in walk(gen.target):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
    return out


def _shape_vary_reason(expr, params: set[str], loop_vars: set[str]) -> str | None:
    for node in walk(expr):
        if isinstance(node, ast.Name) and node.id in loop_vars:
            return f"shape derives from loop variable {node.id!r}"
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len" and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in params):
            return f"shape derives from len({node.args[0].id}) of a caller argument"
        if (isinstance(node, ast.Attribute) and node.attr == "shape"
                and isinstance(node.value, ast.Name)
                and node.value.id in params):
            return f"shape derives from {node.value.id}.shape of a caller argument"
    return None


class _A6:
    id = "A6"
    summary = "unbounded or oversized jit signature family (recompile hazard)"
    hint = ("every distinct abstract signature is a separate XLA "
            "compilation: pad/bucket shapes, hoist shape-varying "
            "constructors out of the call, mark Python-control-flow "
            "parameters static_argnums (and keep statics hashable)")
    #: census threshold: more distinct call-site signatures than this for
    #: one program flags even when each is individually bounded
    K = 8

    def check(self, analysis: Analysis) -> None:
        dm = analysis.project.device_model()
        for w in dm.wrappers:
            sites = dm.call_sites(w)
            sigs: dict[tuple, tuple[FuncDef, ast.Call]] = {}
            for ctx, call in sites:
                sig = self._site(analysis, w, ctx, call)
                if sig is not None:
                    sigs.setdefault(sig, (ctx, call))
            if len(sigs) > self.K:
                chain = tuple(
                    Step(ctx.module.relpath, call.lineno,
                         f"signature #{i + 1}", False)
                    for i, (ctx, call) in enumerate(list(sigs.values())[:4])
                )
                analysis.findings.append(Finding(
                    w.relpath, w.line, 0, self.id,
                    f"jitted {w.name!r} sees {len(sigs)} distinct call-site "
                    f"signatures (> {self.K}): each one compiles separately",
                    chain,
                ))
            self._missing_static(analysis, w)

    def _site(self, analysis: Analysis, w: JitWrapper, ctx: FuncDef,
              call: ast.Call) -> tuple | None:
        off = w.self_offset(call)
        params = {a.arg for a in [*ctx.node.args.posonlyargs,
                                  *ctx.node.args.args]}
        loops = _loop_vars(ctx, call)
        parts: list[tuple] = []
        pnames = w.param_names
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                return None
            pos = i - off
            parts.append((pos, self._descr(
                analysis, w, ctx, call, arg, pos, params, loops)))
        for kw in call.keywords:
            if kw.arg is None:
                return None
            pos = pnames.index(kw.arg) if kw.arg in pnames else kw.arg
            parts.append((pos, self._descr(
                analysis, w, ctx, call, kw.value,
                pos if isinstance(pos, int) else -1, params, loops)))
        return tuple(sorted(parts, key=lambda p: str(p[0])))

    def _descr(self, analysis, w: JitWrapper, ctx, call, arg, pos,
               params, loops) -> str:
        is_static = (isinstance(pos, int) and pos in w.static) or (
            pos in w.static_names if isinstance(pos, str) else False)
        if is_static:
            return self._static_descr(analysis, w, ctx, call, arg, params, loops)
        return self._traced_descr(analysis, w, ctx, call, arg, params, loops)

    def _static_descr(self, analysis, w, ctx, call, arg, params, loops) -> str:
        if isinstance(arg, ast.Constant):
            return f"s:{arg.value!r}"
        if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
            self._flag(analysis, w, ctx, call, arg,
                       "unhashable literal at a static_argnums position "
                       "(TypeError at dispatch, or a cache miss per call)")
            return f"s:{_fp(arg)}"
        for node in walk(arg):
            if isinstance(node, ast.Name) and node.id in loops:
                self._flag(analysis, w, ctx, call, arg,
                           f"static argument varies with loop variable "
                           f"{node.id!r}: one compilation per iteration")
                break
            if isinstance(node, ast.Name) and node.id in params:
                self._flag(analysis, w, ctx, call, arg,
                           f"static argument derives from caller argument "
                           f"{node.id!r}: one compilation per distinct value")
                break
        return f"s:{_fp(arg)}"

    def _traced_descr(self, analysis, w, ctx, call, arg, params, loops) -> str:
        if isinstance(arg, ast.Constant):
            return f"py:{type(arg.value).__name__}"
        if isinstance(arg, (ast.Tuple, ast.List)):
            return "(" + ",".join(
                self._traced_descr(analysis, w, ctx, call, e, params, loops)
                for e in arg.elts) + ")"
        if isinstance(arg, ast.Dict):
            for k in arg.keys:
                if not isinstance(k, ast.Constant):
                    self._flag(analysis, w, ctx, call, arg,
                               "pytree dict keys computed at runtime: the "
                               "tree structure (and so the signature) is "
                               "unbounded")
                    return "dict:?"
            keys = sorted(repr(k.value) for k in arg.keys)
            return "dict:[" + ",".join(keys) + "]"
        if isinstance(arg, ast.Call):
            name = (ctx.module.imports.resolve_node(arg.func) or
                    dotted_name(arg.func) or "")
            last = name.rsplit(".", 1)[-1]
            if last in _ARRAY_CTORS:
                shape_args = arg.args if last == "arange" else arg.args[:1]
                for sa in shape_args:
                    reason = _shape_vary_reason(sa, params, loops)
                    if reason is not None:
                        self._flag(analysis, w, ctx, call, arg,
                                   f"shape-varying constructor: {reason}")
                        break
                return f"ctor:{last}:{_fp(arg)}"
            return f"call:{_fp(arg)}"
        return f"sym:{_fp(arg)}"

    def _flag(self, analysis, w: JitWrapper, ctx, call, arg, why: str) -> None:
        analysis.findings.append(Finding(
            ctx.module.relpath, call.lineno, call.col_offset, self.id,
            f"unbounded signature family for jitted {w.name!r}: {why}",
            (Step(w.relpath, w.line, f"jit constructed here [{w.name}]",
                  False),),
        ))

    def _missing_static(self, analysis: Analysis, w: JitWrapper) -> None:
        """A traced parameter steering Python control flow inside the
        wrapped body either crashes at trace time or (a Python scalar fed
        per call) bakes one compilation per distinct value."""
        if w.fn_node is None:
            return
        params = w.param_names
        traced = {
            p for i, p in enumerate(params)
            if i not in w.static and p not in w.static_names and p != "self"
        }
        if not traced:
            return
        for node in walk(w.fn_node):
            tests: list = []
            if isinstance(node, (ast.If, ast.While)):
                tests.append(node.test)
            elif (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                    and node.func.id == "range"):
                tests.extend(node.args)
            for t in tests:
                hit = self._bare_param(t, traced)
                if hit is None:
                    continue
                analysis.findings.append(Finding(
                    w.relpath, w.line, 0, self.id,
                    f"traced parameter {hit!r} of jitted {w.name!r} drives "
                    "Python control flow: mark it static_argnums (or it "
                    "compiles per value / fails to trace)",
                    (Step(w.relpath, t.lineno, f"{hit!r} used here", True),),
                ))
                return  # one finding per program is the actionable unit

    def _bare_param(self, expr, traced: set[str]) -> str | None:
        """A bare Name load of a traced param — skipping Attribute bases
        (``x.shape[0]`` is static under trace) and ``is None`` checks
        (structure, not value)."""
        if isinstance(expr, ast.Attribute):
            return None
        if (isinstance(expr, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops)
                and all(isinstance(c, ast.Constant) and c.value is None
                        for c in expr.comparators)):
            return None
        if isinstance(expr, ast.Name):
            return expr.id if expr.id in traced else None
        for child in ast.iter_child_nodes(expr):
            hit = self._bare_param(child, traced)
            if hit is not None:
                return hit
        return None


# ---- A7: host sync reachable from hot paths ------------------------------

_SYNC_METHODS = {
    "item": "per-element device->host transfer",
    "tolist": "full device->host transfer",
    "block_until_ready": "host stalls until the device drains",
}
_SYNC_FUNCS = {
    "jax.block_until_ready": "host stalls until the device drains",
    "jax.device_get": "device->host transfer",
}
_CAST_FUNCS = {"float", "int", "numpy.asarray", "numpy.array"}


def _j1_scope(relpath: str) -> bool:
    return "dmlc_tpu/parallel/" in relpath or "dmlc_tpu/ops/" in relpath


class _A7:
    id = "A7"
    summary = "host synchronization reachable from a hot path"
    hint = ("the sync stalls every caller of the hot function: move the "
            "readback behind the pipeline's designed sync point (or out of "
            "the hot path entirely), or justify with '# dmlc-lint: "
            "disable=A7 -- why' at the sync site")

    def check(self, analysis: Analysis) -> None:
        project = analysis.project
        dm = project.device_model()
        seen: set[tuple[str, int]] = set()
        jit_lines: dict[str, set[int]] = {}
        for hot in dm.hot_funcs():
            for ctx, stmts, chain in project.reachable_contexts(
                    hot, hot.node.body):
                rel = ctx.module.relpath
                if _j1_scope(rel) and rel not in jit_lines:
                    jit_lines[rel] = dm.jit_body_lines(rel)
                owned = jit_lines.get(rel, set())
                results = self._jit_result_names(ctx, dm)
                for call in iter_calls(stmts):
                    why = self._sync_reason(call, ctx, dm, results)
                    if why is None or call.lineno in owned:
                        continue
                    key = (rel, call.lineno)
                    if key in seen:
                        continue
                    seen.add(key)
                    analysis.findings.append(Finding(
                        rel, call.lineno, call.col_offset, self.id,
                        f"{why}, reached from hot path "
                        f"{hot.name!r} ({hot.module.relpath})",
                        chain,
                    ))
                for line, why in self._control_flow_syncs(stmts, results):
                    if line in owned or (rel, line) in seen:
                        continue
                    seen.add((rel, line))
                    analysis.findings.append(Finding(
                        rel, line, 0, self.id,
                        f"{why}, reached from hot path "
                        f"{hot.name!r} ({hot.module.relpath})",
                        chain,
                    ))

    @staticmethod
    def _jit_result_names(ctx: FuncDef, dm) -> set[str]:
        out: set[str] = set()
        for node in walk(ctx.node):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            if dm.wrapper_for_call(node.value, ctx) is None:
                continue
            for t in node.targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for e in elts:
                    if isinstance(e, ast.Name):
                        out.add(e.id)
        return out

    def _sync_reason(self, call: ast.Call, ctx: FuncDef, dm,
                     results: set[str]) -> str | None:
        if isinstance(call.func, ast.Attribute) and not call.args:
            why = _SYNC_METHODS.get(call.func.attr)
            if why is not None:
                return f".{call.func.attr}(): {why}"
        name = ctx.module.imports.resolve_node(call.func)
        why = _SYNC_FUNCS.get(name or "")
        if why is not None:
            return f"{name}(): {why}"
        if name in _CAST_FUNCS and call.args:
            a = call.args[0]
            if isinstance(a, ast.Name) and a.id in results:
                return (f"{name}() on jit result {a.id!r}: blocks on the "
                        "device and copies to host")
            if isinstance(a, ast.Call) and dm.wrapper_for_call(a, ctx):
                return (f"{name}() directly on a jit call result: blocks on "
                        "the device and copies to host")
        return None

    @staticmethod
    def _control_flow_syncs(stmts, results: set[str]):
        """``if out[0] > t:`` / ``while flag:`` on a jit result — bool()
        forces the device->host sync inside the control decision."""
        if not results:
            return
        for node in walk(ast.Module(body=list(stmts), type_ignores=[])):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if not isinstance(node, (ast.If, ast.While)):
                continue
            for sub in walk(node.test):
                if (isinstance(sub, ast.Subscript)
                        and isinstance(sub.value, ast.Name)
                        and sub.value.id in results):
                    yield (node.lineno,
                           f"Python control flow on indexed jit result "
                           f"{sub.value.id!r}: bool() is a device sync")
                    break
                if isinstance(sub, ast.Name) and sub.id in results \
                        and sub is node.test:
                    yield (node.lineno,
                           f"Python control flow on jit result {sub.id!r}: "
                           "bool() is a device sync")
                    break


# ---- A8: mesh / PartitionSpec consistency --------------------------------

_COLLECTIVE_LAST = {"psum", "pmean", "pmax", "pmin", "axis_index",
                    "all_gather", "all_to_all", "ppermute"}


def _is_spec_call(call: ast.Call, imports) -> bool:
    name = imports.resolve_node(call.func) or ""
    return name.rsplit(".", 1)[-1] == "PartitionSpec"


def _literal_axes(call: ast.Call):
    """(axis, node) for every literal axis name in a PartitionSpec call."""
    for a in call.args:
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            yield a.value, a
        elif isinstance(a, (ast.Tuple, ast.List)):
            for e in a.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    yield e.value, e


class _A8:
    id = "A8"
    summary = "PartitionSpec/collective axis not on the enclosing mesh"
    hint = ("axis names must be declared by the mesh the spec runs under "
            "(Mesh(..., axis_names=...) / make_mesh({...})); keep spec "
            "entries within the operand's rank and in_specs arity equal to "
            "the operand count")

    def check(self, analysis: Analysis) -> None:
        project = analysis.project
        dm = project.device_model()
        for mod in project.modules.values():
            shard_calls = []
            for fd in project._all_funcs(mod):
                for node in walk(fd.node):
                    if not isinstance(node, ast.Call):
                        continue
                    name = mod.imports.resolve_node(node.func) or ""
                    if name.rsplit(".", 1)[-1] == "shard_map":
                        shard_calls.append((fd, node))
                        self._check_shard_map(analysis, dm, fd, node)
                    elif name.rsplit(".", 1)[-1] == "NamedSharding":
                        self._check_named_sharding(analysis, dm, fd, node)
            self._check_collectives(analysis, dm, mod, shard_calls)
            self._check_rule_tables(analysis, mod)

    # -- shard_map sites ---------------------------------------------------

    @staticmethod
    def _sm_parts(call: ast.Call):
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        mesh = kw.get("mesh") or (call.args[1] if len(call.args) > 1 else None)
        in_specs = kw.get("in_specs") or (call.args[2] if len(call.args) > 2 else None)
        out_specs = kw.get("out_specs") or (call.args[3] if len(call.args) > 3 else None)
        body = call.args[0] if call.args else None
        return body, mesh, in_specs, out_specs

    def _check_shard_map(self, analysis, dm, fd: FuncDef, call: ast.Call) -> None:
        body, mesh_expr, in_specs, out_specs = self._sm_parts(call)
        md = dm.resolve_mesh(mesh_expr, fd) if mesh_expr is not None else None
        if md is not None:
            for spec_expr in (in_specs, out_specs):
                for axis, node in self._spec_axes(spec_expr, fd):
                    if axis not in md.axes:
                        analysis.findings.append(Finding(
                            fd.module.relpath, node.lineno, node.col_offset,
                            self.id,
                            f"shard_map spec names axis {axis!r} but the "
                            f"mesh declares {md.axes}",
                            (Step(md.relpath, md.line, "mesh defined here",
                                  False),),
                        ))
        # arity + rank against the immediate call's operands, mesh or not
        outer = self._immediate_call(fd, call)
        if outer is None or not isinstance(in_specs, (ast.Tuple, ast.List)):
            return
        if any(isinstance(a, ast.Starred) for a in outer.args) or outer.keywords:
            return
        if len(in_specs.elts) != len(outer.args):
            analysis.findings.append(Finding(
                fd.module.relpath, call.lineno, call.col_offset, self.id,
                f"in_specs has {len(in_specs.elts)} entries but the call "
                f"passes {len(outer.args)} operands",
            ))
            return
        for spec_e, operand in zip(in_specs.elts, outer.args):
            spec_call = self._as_spec_call(spec_e, fd)
            if spec_call is None:
                continue
            rank = self._operand_rank(operand, fd)
            if rank is not None and len(spec_call.args) > rank:
                analysis.findings.append(Finding(
                    fd.module.relpath, spec_e.lineno, spec_e.col_offset,
                    self.id,
                    f"PartitionSpec has {len(spec_call.args)} entries for "
                    f"operand {_display(operand)!r} of rank {rank}",
                ))

    @staticmethod
    def _immediate_call(fd: FuncDef, inner: ast.Call) -> ast.Call | None:
        for node in walk(fd.node):
            if isinstance(node, ast.Call) and node.func is inner:
                return node
        return None

    def _spec_axes(self, expr, fd: FuncDef, _depth: int = 2):
        if expr is None or _depth < 0:
            return
        if isinstance(expr, (ast.Tuple, ast.List)):
            for e in expr.elts:
                yield from self._spec_axes(e, fd, _depth)
            return
        if isinstance(expr, ast.Call) and _is_spec_call(expr, fd.module.imports):
            yield from _literal_axes(expr)
            return
        if isinstance(expr, ast.Name):
            bound = self._local_binding(fd, expr.id)
            if bound is not None:
                yield from self._spec_axes(bound, fd, _depth - 1)

    def _as_spec_call(self, expr, fd: FuncDef) -> ast.Call | None:
        if isinstance(expr, ast.Call) and _is_spec_call(expr, fd.module.imports):
            return expr
        if isinstance(expr, ast.Name):
            bound = self._local_binding(fd, expr.id)
            if isinstance(bound, ast.Call) and _is_spec_call(
                    bound, fd.module.imports):
                return bound
        return None

    @staticmethod
    def _local_binding(fd: FuncDef, name: str):
        """The single assignment to ``name`` in this function, else None
        (two bindings = not statically certain, stay silent)."""
        found = None
        for node in walk(fd.node):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == name):
                if found is not None:
                    return None
                found = node.value
        return found

    def _operand_rank(self, operand, fd: FuncDef) -> int | None:
        expr = operand
        if isinstance(operand, ast.Name):
            expr = self._local_binding(fd, operand.id)
        if not isinstance(expr, ast.Call):
            return None
        name = fd.module.imports.resolve_node(expr.func) or ""
        if name.rsplit(".", 1)[-1] not in {"zeros", "ones", "full", "empty"}:
            return None
        if expr.args and isinstance(expr.args[0], (ast.Tuple, ast.List)):
            return len(expr.args[0].elts)
        return None

    # -- NamedSharding -----------------------------------------------------

    def _check_named_sharding(self, analysis, dm, fd: FuncDef,
                              call: ast.Call) -> None:
        if len(call.args) < 2:
            return
        md = dm.resolve_mesh(call.args[0], fd)
        if md is None:
            return
        for axis, node in self._spec_axes(call.args[1], fd):
            if axis not in md.axes:
                analysis.findings.append(Finding(
                    fd.module.relpath, node.lineno, node.col_offset, self.id,
                    f"NamedSharding spec names axis {axis!r} but the mesh "
                    f"declares {md.axes}",
                    (Step(md.relpath, md.line, "mesh defined here", False),),
                ))

    # -- partition-rule tables (parallel/sharding.py grammar) --------------
    #
    # A rule table is a literal tuple/list of (regex-string, PartitionSpec)
    # pairs, matched first-match-wins against '/'-joined param paths
    # (sharding.match_partition_rules). Static defects found here are
    # SILENT at runtime until the wrong mesh shape: a dead rule means some
    # param silently falls through to a later (usually replicated) spec,
    # and a table with no terminal catch-all leaves params spec-less at
    # mesh>1 — strict matching raises, non-strict silently replicates.
    # The dynamic complement is sharding.validate_rules, which checks a
    # table against a REAL param tree; this static half needs no model.

    def _rule_table_entries(self, value, imports):
        """[(pattern_const, spec_call)] when ``value`` is a literal rule
        table, else None. Every element must fit the grammar — one odd
        element means it is some other data structure, stay silent."""
        if not isinstance(value, (ast.Tuple, ast.List)) or not value.elts:
            return None
        entries = []
        for e in value.elts:
            if (not isinstance(e, (ast.Tuple, ast.List)) or len(e.elts) != 2
                    or not isinstance(e.elts[0], ast.Constant)
                    or not isinstance(e.elts[0].value, str)
                    or not isinstance(e.elts[1], ast.Call)
                    or not _is_spec_call(e.elts[1], imports)):
                return None
            entries.append((e.elts[0], e.elts[1]))
        return entries

    def _check_rule_tables(self, analysis: Analysis, mod) -> None:
        import re as re_mod

        # Rule tables are declared as module- or class-level constants; a
        # full ast.walk here measurably blows the whole-tree runtime budget.
        stmts = list(mod.tree.body)
        stmts.extend(
            s for n in mod.tree.body if isinstance(n, ast.ClassDef)
            for s in n.body
        )
        for node in stmts:
            if not isinstance(node, ast.Assign):
                continue
            entries = self._rule_table_entries(node.value, mod.imports)
            if entries is None:
                continue
            seen: dict[str, int] = {}
            catchall: tuple[int, str] | None = None
            for i, (pat_node, _spec) in enumerate(entries):
                pat = pat_node.value
                try:
                    re_mod.compile(pat)
                except re_mod.error as exc:
                    analysis.findings.append(Finding(
                        mod.relpath, pat_node.lineno, pat_node.col_offset,
                        self.id,
                        f"partition rule regex {pat!r} does not compile: "
                        f"{exc} — every param matches a LATER rule or none",
                    ))
                    continue
                if catchall is not None:
                    analysis.findings.append(Finding(
                        mod.relpath, pat_node.lineno, pat_node.col_offset,
                        self.id,
                        f"partition rule {pat!r} is dead: shadowed by "
                        f"catch-all {catchall[1]!r} at entry {catchall[0]} "
                        f"(first match wins)",
                    ))
                    continue
                if pat in seen:
                    analysis.findings.append(Finding(
                        mod.relpath, pat_node.lineno, pat_node.col_offset,
                        self.id,
                        f"partition rule {pat!r} duplicates entry {seen[pat]}"
                        f" — the later rule is dead (first match wins)",
                    ))
                    continue
                seen[pat] = i
                if pat in ("", ".*"):
                    catchall = (i, pat)
            if catchall is None:
                analysis.findings.append(Finding(
                    mod.relpath, node.lineno, node.col_offset, self.id,
                    "partition rule table has no terminal catch-all "
                    "('.*'): params matched by no rule are SPEC-LESS at "
                    "mesh>1 (strict matching raises; non-strict silently "
                    "replicates)",
                ))

    # -- collectives -------------------------------------------------------

    def _check_collectives(self, analysis, dm, mod, shard_calls) -> None:
        for fd in self._mod_funcs(mod):
            encl = self._enclosing_defs(fd.node)
            for node in walk(fd.node):
                if not isinstance(node, ast.Call):
                    continue
                name = mod.imports.resolve_node(node.func) or ""
                last = name.rsplit(".", 1)[-1]
                if last not in _COLLECTIVE_LAST or ".lax" not in "." + name:
                    continue
                axis = self._collective_axis(node, last)
                if axis is None:
                    continue
                owner = encl.get(id(node), fd.node.name)
                axes_sets = self._binding_axes(dm, mod, shard_calls, owner)
                if not axes_sets:
                    continue
                if any(axis in axes for axes in axes_sets):
                    continue
                declared = sorted({a for axes in axes_sets for a in axes})
                analysis.findings.append(Finding(
                    mod.relpath, node.lineno, node.col_offset, self.id,
                    f"{last}(axis {axis!r}) inside {owner!r} but its "
                    f"shard_map mesh declares {tuple(declared)}",
                ))

    @staticmethod
    def _mod_funcs(mod):
        yield from mod.functions.values()
        for ci in mod.classes.values():
            yield from ci.methods.values()

    @staticmethod
    def _enclosing_defs(root) -> dict[int, str]:
        """id(node) -> name of the innermost enclosing def under ``root``."""
        out: dict[int, str] = {}

        def visit(node, owner):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    visit(child, child.name)
                else:
                    out[id(child)] = owner
                    visit(child, owner)

        visit(root, root.name)
        return out

    @staticmethod
    def _collective_axis(call: ast.Call, last: str) -> str | None:
        cand = next((k.value for k in call.keywords if k.arg == "axis_name"),
                    None)
        if cand is None:
            idx = 0 if last == "axis_index" else 1
            if len(call.args) > idx:
                cand = call.args[idx]
        if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
            return cand.value
        return None

    def _binding_axes(self, dm, mod, shard_calls, owner: str) -> list[tuple]:
        """Known axis sets of shard_map sites whose body function is
        ``owner`` (by name, directly or through functools.partial)."""
        out = []
        for fd, call in shard_calls:
            body, mesh_expr, _, _ = self._sm_parts(call)
            if isinstance(body, ast.Call):
                bname = (mod.imports.resolve_node(body.func) or "")
                if bname.rsplit(".", 1)[-1] == "partial" and body.args:
                    body = body.args[0]
            ref = dotted_name(body) if body is not None else None
            if ref is None or ref.rsplit(".", 1)[-1] != owner:
                continue
            md = dm.resolve_mesh(mesh_expr, fd) if mesh_expr is not None else None
            if md is not None:
                out.append(md.axes)
        return out


A5 = _A5()
A6 = _A6()
A7 = _A7()
A8 = _A8()
