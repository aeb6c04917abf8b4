"""Source-level meta-checks: defects in *this repo's own code* rather than
in a traced program.

* :func:`swallowed_failures` — ``except Exception:`` / bare ``except:``
  handlers that neither re-raise nor report: the handler converts a real
  failure into silence, the exact anti-pattern a typed error interface
  exists to kill.  A handler is fine if its body re-raises (``raise``),
  prints the traceback (top-level CLI guard), or the ``except`` line carries
  ``# lint: allow-broad-except`` with a justification.
* :func:`unregistered_pvars` — every *literal* pvar name passed to
  ``tool.pvar_count`` / ``tool.pvar_add`` in the tree must be registered in
  ``tool.PVARS`` (``pvar_register``): an undocumented counter is invisible
  to ``pvar_info`` and drifts silently.  Dynamically-formatted names
  (f-strings in the facade binder) are covered at runtime by
  ``tool.pvar_strict`` instead.
* :func:`unregistered_spans` — the same audit for span names: every literal
  name passed to ``tool.span`` (under any name its module imports it as)
  must be ``span_register``ed in ``tool.SPANS``, so that ``span_info``
  lists every span a trace can hold.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, Iterator

from repro.analysis.checkers import Finding
from repro.core.errors import ErrorClass

ALLOW_PRAGMA = "lint: allow-broad-except"


def _py_files(paths: Iterable[str | Path]) -> Iterator[Path]:
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            yield p


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True                       # bare except:
    if isinstance(t, ast.Name) and t.id in ("Exception", "BaseException"):
        return True
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and e.id in ("Exception", "BaseException")
                   for e in t.elts)
    return False


def _reports_or_reraises(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in ("print_exc", "format_exc"):
                return True
    return False


def swallowed_failures(paths: Iterable[str | Path]) -> list[Finding]:
    findings: list[Finding] = []
    for path in _py_files(paths):
        src = path.read_text()
        lines = src.splitlines()
        try:
            tree = ast.parse(src, filename=str(path))
        except SyntaxError as exc:
            findings.append(Finding(
                ErrorClass.ERR_OTHER, "syntax",
                f"unparseable: {exc}", f"{path}",
            ))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler) or not _is_broad(node):
                continue
            line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
            if ALLOW_PRAGMA in line:
                continue
            if _reports_or_reraises(node):
                continue
            what = "bare except" if node.type is None else "except Exception"
            findings.append(Finding(
                ErrorClass.ERR_OTHER, "swallowed-failure",
                f"{what} swallows the error without re-raising or reporting "
                f"— catch the specific expected exception and let the rest "
                f"propagate", f"{path}:{node.lineno}",
            ))
    return findings


def _literal_names(paths: Iterable[str | Path], match) -> list[tuple[str, str]]:
    """(name, file:line) for every call whose first argument is a string
    literal and whose callee ``match(tree)`` accepts, ``tree`` being the
    module the call is in."""

    writes: list[tuple[str, str]] = []
    for path in _py_files(paths):
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError:
            continue                      # reported by swallowed_failures
        accepts = match(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args or not accepts(node.func):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                writes.append((arg.value, f"{path}:{node.lineno}"))
    return writes


def _pvar_writes(tree: ast.Module):
    def accepts(fn: ast.expr) -> bool:
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        return name in ("pvar_count", "pvar_add")

    return accepts


def _dotted(node: ast.expr) -> str:
    """``a.b.c`` for a chain of names and attributes, else ``""``."""

    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        head = _dotted(node.value)
        return f"{head}.{node.attr}" if head else ""
    return ""


def _tool_spans(tree: ast.Module):
    """Calls of ``repro.core.tool.span`` under whatever name the module
    binds it to: ``tool.span`` (``from repro.core import tool [as t]``,
    ``import repro.core.tool [as t]``) or a bare ``span`` (``from
    repro.core.tool import span [as s]``)."""

    modules, funcs = {"tool"}, set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            where = "." * node.level + (node.module or "")
            for a in node.names:
                if a.name == "tool" and where in ("repro.core", "."):
                    modules.add(a.asname or a.name)
                elif a.name == "span" and where in ("repro.core.tool", ".tool"):
                    funcs.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "repro.core.tool":
                    modules.add(a.asname or a.name)

    def accepts(fn: ast.expr) -> bool:
        if isinstance(fn, ast.Name):
            return fn.id in funcs
        return (isinstance(fn, ast.Attribute) and fn.attr == "span"
                and _dotted(fn.value) in modules)

    return accepts


def _import_registries() -> None:
    # importing the runtime/checkpoint layers runs their module-level
    # pvar_register/span_register calls, populating the registries the
    # audits compare against
    import repro.checkpoint.manager   # noqa: F401
    import repro.core                 # noqa: F401
    import repro.runtime.engine       # noqa: F401
    import repro.runtime.kvpool       # noqa: F401
    import repro.runtime.server       # noqa: F401
    import repro.runtime.trainer      # noqa: F401
    import repro.tune                 # noqa: F401


def unregistered_pvars(paths: Iterable[str | Path]) -> list[Finding]:
    _import_registries()
    from repro.core import tool

    findings: list[Finding] = []
    for name, where in _literal_names(paths, _pvar_writes):
        if name not in tool.PVARS:
            findings.append(Finding(
                ErrorClass.ERR_ARG, "unregistered-pvar",
                f"pvar {name!r} is written but never pvar_register()ed — "
                f"undocumented counters are invisible to pvar_info and "
                f"drift silently", where,
            ))
    return findings


def unregistered_spans(paths: Iterable[str | Path]) -> list[Finding]:
    _import_registries()
    from repro.core import tool

    return [
        Finding(
            ErrorClass.ERR_ARG, "unregistered-span",
            f"span {name!r} is opened but never span_register()ed — "
            f"span_info would not list it", where,
        )
        for name, where in _literal_names(paths, _tool_spans)
        if name not in tool.SPANS
    ]


def run_static(paths: Iterable[str | Path]) -> list[Finding]:
    paths = list(paths)
    return swallowed_failures(paths) + unregistered_pvars(paths) + unregistered_spans(paths)
