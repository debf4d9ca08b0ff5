#!/usr/bin/env python
"""Append-only telemetry schema lint (ISSUE 11 satellite; an AST
lint against a committed registry, applied to the JSONL stream).

The telemetry stream's contract is APPEND-ONLY: fields may be added,
never renamed or removed — consumers (scripts/telemetry_report.py,
telemetry/aggregate.py, external dashboards) parse by literal field
name, so a rename breaks them SILENTLY at read time.  This lint makes
that a tier-1 failure at WRITE time instead (tests/test_programs.py):

  1. every emitted ``kind`` must be registered in
     ``telemetry.recorder.TELEMETRY_SCHEMA``;
  2. every emitted field of a CLOSED kind must be in the kind's
     registered field set — a renamed/new field fails until the
     registry (the documented contract) is updated with it;
  3. a ``**splat`` into ``record_event`` on a closed kind must be
     resolvable (a local dict built from literal keys, or a call listed
     in ``_SPLAT_SOURCES`` whose field vocabulary is a committed module
     constant) — otherwise the lint cannot see what is emitted and says
     so, instead of silently under-checking;
  4. every registered kind must be emitted somewhere (unless listed in
     ``telemetry.recorder.RETIRED_KINDS``) — the registry cannot rot
     into fiction.

Emission sites recognized (AST scan of every .py under the package):
``<recorder>.record_event("<kind>", field=..., **local_dict)`` calls,
and dict literals carrying a literal ``"kind"`` entry (the recorder's
own record_step/record_span bodies) plus literal-key subscript
assignments onto the same variable in the same function.

Run:  python scripts/check_telemetry_schema.py   (exit 0 = clean)
"""

from __future__ import annotations

import ast
import glob
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)

PACKAGE_DIR = os.path.join(_REPO, "faster_distributed_training_tpu")

# **splat calls whose emitted field vocabulary is a committed module
# constant: {final callable name: (module, attribute holding the field
# names)}.  state_bytes_table's keys ARE programs.STATE_MEMORY_FIELDS
# by construction — renaming a key there without the registry (or
# vice versa) fails rule 2/3.
_SPLAT_SOURCES = {
    "state_bytes_table": (
        "faster_distributed_training_tpu.telemetry.programs",
        "STATE_MEMORY_FIELDS"),
}


def _lit_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _call_name(func) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


class _Emission:
    def __init__(self, kind: str, fields: Set[str], where: str,
                 unresolved: List[str]):
        self.kind = kind
        self.fields = fields
        self.where = where
        self.unresolved = unresolved


def _scope_walk(scope):
    """Walk one scope's OWN statements, excluding nested function
    subtrees — two functions that both name a local ``rec``/``ev`` must
    not have their dict keys merged."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def _scope_dict_vars(scope) -> Tuple[Dict[str, Set[str]],
                                     Dict[str, str]]:
    """Within one function (or module) scope: {var: literal keys} for
    dict-literal assignments + literal-key subscript assigns, and
    {var: kind} for dicts that carry a literal "kind" entry."""
    var_fields: Dict[str, Set[str]] = {}
    var_kind: Dict[str, str] = {}
    for node in _scope_walk(scope):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
            keys = set()
            kind = None
            for k, v in zip(node.value.keys, node.value.values):
                ks = _lit_str(k) if k is not None else None
                if ks is None:
                    continue
                if ks == "kind":
                    kind = _lit_str(v)
                else:
                    keys.add(ks)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    var_fields.setdefault(tgt.id, set()).update(keys)
                    if kind is not None:
                        var_kind[tgt.id] = kind
        if (isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)
                and isinstance(node.targets[0].value, ast.Name)):
            key = _lit_str(node.targets[0].slice)
            if key is not None and key != "kind":
                var_fields.setdefault(
                    node.targets[0].value.id, set()).add(key)
    return var_fields, var_kind


def _resolve_splat(value, var_fields) -> Optional[Set[str]]:
    """Field set a ``**value`` splat contributes, or None when the lint
    cannot know (rule 3 decides whether that matters)."""
    if isinstance(value, ast.Name) and value.id in var_fields:
        return set(var_fields[value.id])
    if isinstance(value, ast.Call):
        src = _SPLAT_SOURCES.get(_call_name(value.func))
        if src is not None:
            import importlib
            mod = importlib.import_module(src[0])
            return set(getattr(mod, src[1])) - {"kind"}
    return None


def default_paths() -> List[str]:
    """Every .py in the package — the default scan surface (tests
    extend it with violation fixtures)."""
    return sorted(
        p for p in glob.glob(os.path.join(PACKAGE_DIR, "**", "*.py"),
                             recursive=True)
        if "__pycache__" not in p)


def scan_emissions(paths: Optional[List[str]] = None) -> List[_Emission]:
    """Every telemetry emission the AST scan can see across ``paths``
    (default: the whole package)."""
    if paths is None:
        paths = default_paths()
    out: List[_Emission] = []
    seen = set()
    for path in paths:
        with open(path) as fh:
            tree = ast.parse(fh.read())
        rel = os.path.relpath(path, _REPO)
        scopes = [tree] + [n for n in ast.walk(tree)
                           if isinstance(n, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
        for scope in scopes:
            var_fields, var_kind = _scope_dict_vars(scope)
            for node in _scope_walk(scope):
                if (isinstance(node, ast.Call)
                        and _call_name(node.func) == "record_event"
                        and node.args):
                    kind = _lit_str(node.args[0])
                    if kind is None:
                        continue
                    key = (rel, node.lineno, kind)
                    if key in seen:    # nested scopes re-walk their body
                        continue
                    seen.add(key)
                    fields: Set[str] = set()
                    unresolved: List[str] = []
                    for kw in node.keywords:
                        if kw.arg is not None:
                            fields.add(kw.arg)
                            continue
                        got = _resolve_splat(kw.value, var_fields)
                        if got is None:
                            unresolved.append(ast.dump(kw.value)[:60])
                        else:
                            fields.update(got)
                    out.append(_Emission(kind, fields,
                                         f"{rel}:{node.lineno}",
                                         unresolved))
            # dict literals carrying "kind" (record_step/record_span
            # bodies): fields = literal keys + subscript assigns on the
            # holding variable in this scope
            for var, kind in var_kind.items():
                key = (rel, id(scope), var, kind)
                if key in seen:
                    continue
                seen.add(key)
                out.append(_Emission(kind,
                                     set(var_fields.get(var, ())),
                                     f"{rel} (dict var {var!r})", []))
            # ...and anonymous kind-dict literals (e.g. a flush_stats
            # record appended inline, never bound to a name)
            for node in _scope_walk(scope):
                if not isinstance(node, ast.Dict):
                    continue
                kind = None
                fields: Set[str] = set()
                for k, v in zip(node.keys, node.values):
                    ks = _lit_str(k) if k is not None else None
                    if ks == "kind":
                        kind = _lit_str(v)
                    elif ks is not None:
                        fields.add(ks)
                if kind is None:
                    continue
                key = (rel, node.lineno, node.col_offset, kind)
                if key in seen:
                    continue
                seen.add(key)
                out.append(_Emission(kind, fields,
                                     f"{rel}:{node.lineno}", []))
    return out


def check(paths: Optional[List[str]] = None) -> List[str]:
    """All schema-drift problems found, [] when clean."""
    from faster_distributed_training_tpu.telemetry.recorder import (
        RETIRED_KINDS, TELEMETRY_SCHEMA)

    problems: List[str] = []
    emissions = scan_emissions(paths)
    emitted_kinds = set()
    for em in emissions:
        emitted_kinds.add(em.kind)
        allowed = TELEMETRY_SCHEMA.get(em.kind, -1)
        if allowed == -1:
            problems.append(
                f"{em.where}: emits unregistered kind {em.kind!r} — add "
                f"it (and its fields) to telemetry.recorder."
                f"TELEMETRY_SCHEMA before it can land")
            continue
        if allowed is None:
            continue                       # open kind (e.g. goodput)
        for f in sorted(em.fields - allowed):
            problems.append(
                f"{em.where}: kind {em.kind!r} emits unregistered field "
                f"{f!r} — the schema is append-only: register the NEW "
                f"name (and keep the old one) in TELEMETRY_SCHEMA")
        for u in em.unresolved:
            problems.append(
                f"{em.where}: kind {em.kind!r} takes an unresolvable "
                f"**splat ({u}) — build the dict from literal keys in "
                f"the same function, or register the callable in "
                f"check_telemetry_schema._SPLAT_SOURCES")
    for kind in sorted(set(TELEMETRY_SCHEMA) - emitted_kinds
                       - set(RETIRED_KINDS)):
        problems.append(
            f"TELEMETRY_SCHEMA registers kind {kind!r} but no emission "
            f"site produces it — stale after a removal?  (list it in "
            f"RETIRED_KINDS if the retirement is intentional)")
    return problems


def main() -> int:
    problems = check()
    if problems:
        for p in problems:
            print(f"[check_telemetry_schema] {p}")
        print(f"[check_telemetry_schema] {len(problems)} problem(s)")
        return 1
    print("[check_telemetry_schema] OK: every emitted kind/field is "
          "registered and every registered kind is emitted")
    return 0


if __name__ == "__main__":
    sys.exit(main())
