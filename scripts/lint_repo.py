#!/usr/bin/env python
"""Repo lint: declared builder effects.

The rule guards the STProve effect substrate: shipped program
*builders* (:data:`EFFECT_DECLARING`) must pass explicit ``reads=`` and
``writes=`` to every ``enqueue_compute`` call.  The no-argument form is
a legal convenience for exploratory user code — the queue substitutes a
conservative reads-everything effect set and flags it ST019 — but in
shipped builders implicit effects over-serialize the happens-before
graph and weaken the race rules (ST015–ST018), so the AST lint bans it
at the source.

Prints ``file:line: message`` per violation and exits non-zero if any are
found (the CI lint job runs this next to ``python -m repro.analysis``).
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: shipped builders where every enqueue_compute must declare its effect
#: set explicitly (reads= AND writes=) — see module docstring
EFFECT_DECLARING = {
    os.path.join("src", "repro", "core", "collectives.py"),
    os.path.join("src", "repro", "core", "halo.py"),
    os.path.join("src", "repro", "launch", "serve.py"),
}


def _implicit_enqueue_compute(node: ast.Call) -> bool:
    """True when this is an ``<q>.enqueue_compute(...)`` call missing an
    explicit ``reads=`` or ``writes=`` keyword."""
    fn = node.func
    if not (isinstance(fn, ast.Attribute) and fn.attr == "enqueue_compute"):
        return False
    if any(isinstance(kw.arg, type(None)) for kw in node.keywords):
        return False  # **kwargs splat: can't see through it statically
    kws = {kw.arg for kw in node.keywords}
    return not {"reads", "writes"} <= kws


def lint_file(path: str, rel: str):
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:  # pragma: no cover - repo must parse
        return [(rel, e.lineno or 0, f"syntax error: {e.msg}")]

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _implicit_enqueue_compute(node):
            out.append((rel, node.lineno,
                        "enqueue_compute without explicit reads=/"
                        "writes= — shipped builders must declare "
                        "effect sets (implicit fallback is ST019)"))
    return out


def main(argv=None) -> int:
    violations = []
    for rel in sorted(EFFECT_DECLARING):
        violations.extend(lint_file(os.path.join(REPO, rel), rel))

    for rel, line, msg in violations:
        print(f"{rel}:{line}: {msg}")
    if violations:
        print(f"lint_repo: {len(violations)} violation(s)", file=sys.stderr)
        return 1
    print("lint_repo: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
