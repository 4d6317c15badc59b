#!/usr/bin/env python3
"""Docs reference check: README/docs must not drift from the code.

Scans ``README.md`` and ``docs/*.md`` for three kinds of references and
fails if any points at something that does not exist:

  * **module paths** — every ``repro.foo.bar[.symbol]`` mention must
    resolve to a module file under ``src/`` (package ``__init__.py``
    included), and a trailing ``.symbol`` must appear as a word in that
    module's source;
  * **CLI flags** — every ``--flag`` mention must be declared by some
    ``add_argument("--flag" ...)`` under ``src/``, ``benchmarks/``,
    ``examples/`` or in ``chip_smoke.py`` (underscore flags like XLA's are exempt — they are
    not argparse surface);
  * **local paths** — markdown links and backtick-quoted paths (with a
    ``/`` and a known extension) must exist on disk.

Pure text analysis — no jax import, runs in milliseconds.  Part of
``scripts/verify.sh`` (both lanes).
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"

MODULE_RE = re.compile(r"\brepro(?:\.[A-Za-z_]\w*)+")
FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9_-]*")
LINK_RE = re.compile(r"\[[^\]]*\]\(([^)#:\s]+)\)")
PATH_RE = re.compile(r"`([\w.-]+(?:/[\w.<>-]+)+\.(?:py|md|sh|json|txt))`")
ADD_ARG_RE = re.compile(r"add_argument\(\s*['\"](--[a-z0-9-]+)['\"]")


def doc_files() -> list[Path]:
    return [ROOT / "README.md"] + sorted((ROOT / "docs").glob("*.md"))


SH_FLAG_RE = re.compile(r"^\s*(--[a-z0-9-]+)\)", re.MULTILINE)


def declared_flags() -> set[str]:
    flags = set()
    for base in (SRC, ROOT / "benchmarks", ROOT / "examples"):
        for py in base.rglob("*.py"):
            flags.update(ADD_ARG_RE.findall(py.read_text()))
    flags.update(ADD_ARG_RE.findall((ROOT / "chip_smoke.py").read_text()))
    for sh in (ROOT / "scripts").glob("*.sh"):   # verify.sh case labels
        flags.update(SH_FLAG_RE.findall(sh.read_text()))
    return flags


def resolve_module(dotted: str) -> str | None:
    """Error string if ``dotted`` does not resolve, else None."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        base = SRC / Path(*parts[:cut])
        mod = base.with_suffix(".py")
        pkg = base / "__init__.py"
        f = mod if mod.exists() else (pkg if pkg.exists() else None)
        if f is None:
            continue
        rest = parts[cut:]
        if not rest:
            return None
        if len(rest) > 1:
            return (f"{dotted}: {'.'.join(parts[:cut])} resolves to "
                    f"{f.relative_to(ROOT)} but the remainder "
                    f"{'.'.join(rest)} nests too deep")
        # the symbol must be *defined or imported* there, not merely a
        # word in prose (a docstring mention would false-pass artifacts
        # like "repro.api.The" from sentence-boundary regex captures)
        sym = re.escape(rest[0])
        defined = re.search(
            rf"(?m)^\s*(?:def|class)\s+{sym}\b"
            rf"|^(?:from\s+\S+\s+)?import\s.*\b{sym}\b"
            rf"|^{sym}\s*[:=]", f.read_text())
        if defined:
            return None
        return (f"{dotted}: symbol {rest[0]!r} is not defined, assigned, "
                f"or imported in {f.relative_to(ROOT)}")
    return f"{dotted}: no module file under src/"


def check() -> int:
    flags = declared_flags()
    errors = []
    for doc in doc_files():
        text = doc.read_text()
        rel = doc.relative_to(ROOT)
        for dotted in sorted(set(MODULE_RE.findall(text))):
            err = resolve_module(dotted)
            if err:
                errors.append(f"{rel}: {err}")
        for flag in sorted(set(FLAG_RE.findall(text))):
            if flag.startswith("--xla"):   # XLA flags, not argparse
                continue
            if flag not in flags:
                errors.append(f"{rel}: CLI flag {flag} is not declared by "
                              f"any add_argument in src/, benchmarks/, "
                              f"examples/ or chip_smoke.py")
        refs = set(LINK_RE.findall(text)) | set(PATH_RE.findall(text))
        for ref in sorted(refs):
            if "<" in ref:             # placeholder paths like step_<n>/
                continue
            if not ((doc.parent / ref).exists() or (ROOT / ref).exists()):
                errors.append(f"{rel}: referenced path {ref} does not exist")
    if errors:
        print(f"[docs-check] {len(errors)} stale reference(s):")
        for e in errors:
            print(f"  - {e}")
        return 1
    n = len(doc_files())
    print(f"[docs-check] OK: {n} docs, {len(flags)} declared flags")
    return 0


if __name__ == "__main__":
    sys.exit(check())
