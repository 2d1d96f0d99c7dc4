"""Documentation gate: markdown link check + docstring lint.

``pydocstyle`` is not installable in the offline container, so this is
the equivalent gate implemented on ``ast``:

1. **Markdown link check** — every relative link/image target in
   ``README.md`` and ``docs/*.md`` must exist on disk (http(s) and
   mailto links are skipped; ``#fragment`` suffixes are stripped).
2. **Docstring lint** over the documented-surface modules
   (``core/scoring.py``, ``core/state.py``, ``core/planner.py``,
   ``core/executor.py``, ``core/costs.py``, ``core/admission.py``,
   ``core/calibration.py``, ``core/frontier_solver.py``,
   ``workflowbench/runner.py``, ``workflowbench/suites.py``): the
   module itself and every PUBLIC
   class, function, method, and property (name not starting with
   ``_``) must carry a docstring whose first paragraph (summary) ends
   with ``.``, ``:``, ``?`` or ``!`` (pydocstyle D1xx presence + a
   wrap-tolerant D400 analogue).

Run from the repo root (CI and ``make docs-check`` do):

    python tools/docs_check.py
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

DOCSTRING_MODULES = [
    "src/repro/core/scoring.py",
    "src/repro/core/state.py",
    "src/repro/core/planner.py",
    "src/repro/core/executor.py",
    "src/repro/core/scheduler.py",
    "src/repro/core/faults.py",
    "src/repro/core/journal.py",
    "src/repro/core/costs.py",
    "src/repro/core/admission.py",
    "src/repro/core/calibration.py",
    "src/repro/core/frontier_solver.py",
    "src/repro/core/policies/__init__.py",
    "src/repro/core/policies/base.py",
    "src/repro/core/policies/fate.py",
    "src/repro/core/policies/baselines.py",
    "src/repro/workflowbench/runner.py",
    "src/repro/workflowbench/suites.py",
    "src/repro/core/routing.py",
    "src/repro/serving/engine.py",
    "src/repro/serving/gateway.py",
    "src/repro/spans.py",
]

MARKDOWN_FILES = ["README.md", *sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md"))]

_LINK_RE = re.compile(r"!?\[[^\]]*\]\(([^)\s]+)(?:\s+\"[^\"]*\")?\)")


def check_markdown(rel: str, errors: list[str]) -> None:
    """Verify every relative link target in one markdown file exists."""
    path = REPO / rel
    if not path.exists():
        errors.append(f"{rel}: file missing")
        return
    text = path.read_text()
    # drop fenced code blocks — their brackets are not links
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for target in _LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        dest = (path.parent / target.split("#", 1)[0]).resolve()
        if not dest.exists():
            errors.append(f"{rel}: broken link -> {target}")


def _ok_docstring(node) -> bool:
    doc = ast.get_docstring(node)
    if not doc or not doc.strip():
        return False
    summary: list[str] = []
    for line in doc.strip().splitlines():
        if not line.strip():
            break
        summary.append(line.strip())
    return " ".join(summary).endswith((".", ":", "?", "!"))


def _public_defs(body, prefix=""):
    """Yield (qualname, node) for public defs, recursing into classes."""
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            if node.name.startswith("_"):
                continue
            qual = f"{prefix}{node.name}"
            yield qual, node
            if isinstance(node, ast.ClassDef):
                yield from _public_defs(node.body, prefix=f"{qual}.")


def check_docstrings(rel: str, errors: list[str]) -> None:
    """pydocstyle-equivalent pass over one module's public surface."""
    path = REPO / rel
    tree = ast.parse(path.read_text())
    if not _ok_docstring(tree):
        errors.append(f"{rel}: module docstring missing/unterminated")
    for qual, node in _public_defs(tree.body):
        if not _ok_docstring(node):
            errors.append(
                f"{rel}:{node.lineno}: {qual}: docstring missing or "
                f"summary paragraph not ending in punctuation")


def main() -> int:
    """Run both gates; print findings; exit nonzero on any."""
    errors: list[str] = []
    for rel in MARKDOWN_FILES:
        check_markdown(rel, errors)
    for rel in DOCSTRING_MODULES:
        check_docstrings(rel, errors)
    if errors:
        print(f"docs check: {len(errors)} problem(s)")
        for e in errors:
            print(f"  {e}")
        return 1
    n_md, n_py = len(MARKDOWN_FILES), len(DOCSTRING_MODULES)
    print(f"docs check: OK ({n_md} markdown files, "
          f"{n_py} docstring-gated modules)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
