"""A4 — RPC frame-field schema: every producer/consumer against one dialect.

The control-plane wire format is a hand-rolled msgpack dict dialect —
request frames ``{m, p, d, t}``, reply frames ``{ok, r, e, retry_after}``
(docs/OVERLOAD.md, OBSERVABILITY.md). Nothing type-checks it: a producer
writing ``frame["dd"]`` or packing a string where every reader expects
seconds ships silently and fails as a hung call or a dropped trace on
another machine.

This rule EXTRACTS the dialect instead of hardcoding it, so it cannot rot:
the module defining ``_send_frame``/``_recv_frame`` (cluster/rpc.py here;
the fixture's mini-fabric in tests) is the schema anchor — every field it
packs or unpacks, with a value type where one is statically concrete, IS
the dialect. Then every frame site project-wide is cross-checked:

- a field not in the dialect → unknown-field finding (the typo class);
- a field read via hard subscript (``req["x"]``) that no producer ever
  writes → missing-field finding (``.get`` reads are optional by design);
- a producer whose concrete value type conflicts with the dialect's
  concrete type → type-conflict finding.

Frame sites are tracked conservatively: dict literals passed to
``_send_frame``, and local variables named like frames (``frame``, ``req``,
``reply``, ``err``, ...) that are either assigned a dict literal or bound
from ``_recv_frame`` — and only in modules that define or import the
pack/unpack helpers, so gossip payloads and ordinary dicts elsewhere are
never dragged into the RPC dialect.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from tools.analyze.core import Analysis, Finding
from tools.analyze.project import ModuleInfo, iter_calls, walk

_FRAME_VARS = {"frame", "req", "reply", "err", "request", "response"}
_PACK, _UNPACK = "_send_frame", "_recv_frame"


@dataclass
class Site:
    module: ModuleInfo
    line: int
    col: int
    kind: str          # "produce" | "consume" | "consume_soft"
    fld: str
    vtype: str | None  # concrete literal type or None


@dataclass
class _Dialect:
    anchor: str                                  # module name of the fabric
    types: dict[str, str | None] = field(default_factory=dict)
    produced: set[str] = field(default_factory=set)


def _value_type(node) -> str | None:
    if isinstance(node, ast.Constant):
        if isinstance(node.value, bool):
            return "bool"
        if isinstance(node.value, str):
            return "str"
        if isinstance(node.value, (int, float)):
            return "num"
        return None
    if isinstance(node, ast.Dict):
        return "dict"
    if isinstance(node, (ast.List, ast.Tuple)):
        return "list"
    return None


def _str_key(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _A4:
    id = "A4"
    summary = "RPC frame field outside the pack/unpack dialect"
    hint = ("the frame dialect is whatever cluster/rpc.py packs and unpacks "
            "— add the field there first (both sides), or fix the typo")

    def check(self, analysis: Analysis) -> None:
        project = analysis.project
        anchor = next(
            (m for m in project.modules.values() if _PACK in m.functions
             or _UNPACK in m.functions),
            None,
        )
        if anchor is None:
            return  # no frame fabric in this package: rule is moot
        sites: list[Site] = []
        for mod in project.modules.values():
            if mod is not anchor and not self._imports_fabric(mod):
                continue
            for fd in project._all_funcs(mod):
                sites.extend(self._collect(mod, fd.node))
        dialect = _Dialect(anchor.name)
        for s in sites:
            if s.module is not anchor:
                continue
            dialect.types.setdefault(s.fld, s.vtype)
            if s.vtype is not None and dialect.types[s.fld] is None:
                dialect.types[s.fld] = s.vtype
            if s.kind == "produce":
                dialect.produced.add(s.fld)
        for s in sites:
            if s.fld not in dialect.types:
                known = ", ".join(sorted(dialect.types))
                analysis.findings.append(Finding(
                    s.module.relpath, s.line, s.col, self.id,
                    f"unknown frame field {s.fld!r} (dialect from "
                    f"{dialect.anchor}: {known})",
                ))
            elif (
                s.kind == "produce"
                and s.vtype is not None
                and dialect.types[s.fld] is not None
                and s.vtype != dialect.types[s.fld]
            ):
                analysis.findings.append(Finding(
                    s.module.relpath, s.line, s.col, self.id,
                    f"frame field {s.fld!r} packed as {s.vtype}, but the "
                    f"dialect carries {dialect.types[s.fld]}",
                ))
            elif s.kind == "consume" and s.fld not in dialect.produced:
                analysis.findings.append(Finding(
                    s.module.relpath, s.line, s.col, self.id,
                    f"frame field {s.fld!r} read via [{s.fld!r}] but no "
                    f"producer ever packs it (use .get() if optional)",
                ))

    @staticmethod
    def _imports_fabric(mod: ModuleInfo) -> bool:
        return any(
            v.split(".")[-1] in (_PACK, _UNPACK) for v in mod.imports.aliases.values()
        )

    def _collect(self, mod: ModuleInfo, fn) -> list[Site]:
        sites: list[Site] = []
        tracked: set[str] = set()

        def add_dict(d: ast.Dict) -> None:
            for k, v in zip(d.keys, d.values):
                key = _str_key(k)
                if key is not None:
                    sites.append(Site(mod, k.lineno, k.col_offset,
                                      "produce", key, _value_type(v)))

        # Pass 1: find tracked frame variables + inline _send_frame dicts.
        for node in walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                continue  # nested defs collected via their own FuncDef pass
            if isinstance(node, ast.Call):
                callee = node.func
                name = callee.attr if isinstance(callee, ast.Attribute) else (
                    callee.id if isinstance(callee, ast.Name) else None
                )
                if name == _PACK:
                    for a in node.args:
                        if isinstance(a, ast.Dict):
                            add_dict(a)
                        elif isinstance(a, ast.Name):
                            tracked.add(a.id)
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                value = node.value
                names = [t.id for t in targets if isinstance(t, ast.Name)]
                # tuple unpack:  req, peer = _recv_frame(...)
                for t in targets:
                    if isinstance(t, ast.Tuple) and t.elts and isinstance(t.elts[0], ast.Name):
                        if self._is_unpack(value):
                            tracked.add(t.elts[0].id)
                if not names:
                    continue
                if isinstance(value, ast.Dict) and any(
                    n in _FRAME_VARS for n in names
                ):
                    tracked.update(n for n in names if n in _FRAME_VARS)
                    add_dict(value)
                elif self._is_unpack(value):
                    tracked.update(names)
        # Pass 2: field accesses/stores on tracked vars.
        for node in walk(fn):
            if isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name):
                if node.value.id not in tracked:
                    continue
                key = _str_key(node.slice)
                if key is None:
                    continue
                kind = "produce" if isinstance(node.ctx, ast.Store) else "consume"
                sites.append(Site(mod, node.lineno, node.col_offset, kind, key, None))
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in tracked
                and node.args
            ):
                key = _str_key(node.args[0])
                if key is not None:
                    sites.append(Site(mod, node.lineno, node.col_offset,
                                      "consume_soft", key, None))
        return sites

    @staticmethod
    def _is_unpack(value) -> bool:
        if not isinstance(value, ast.Call):
            return False
        f = value.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None
        )
        return name == _UNPACK


A4 = _A4()
