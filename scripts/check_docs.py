"""Docs checker: broken intra-repo links and phantom CLI flags.

    python scripts/check_docs.py

Two failure classes, both cheap to detect and historically the two ways
these docs have rotted:

* **Broken intra-repo links** — every markdown link target that is not
  an external URL or a bare anchor must resolve to a real file (relative
  to the doc, or repo-root-relative).  Renaming a doc or module without
  chasing its references fails here.

* **Phantom flags** — every ``--flag`` token mentioned in the docs must
  exist in some repo CLI: the serving/training launchers, the scripts,
  or the benchmarks (collected by scanning their ``add_argument`` calls,
  so the check needs no jax import), plus a small allowlist for
  third-party tools the docs quote (pytest/coverage).  Docs advertising
  a flag ``python -m repro.launch.serve --help`` does not know fail
  here — the bug PR 7/8 reviews kept catching by hand.

Exit status is nonzero on any finding; run it via ``scripts/ci.sh
tier1`` (or ``all``).
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# docs under check: everything in docs/ plus the top-level entry points
DOC_GLOBS = ("docs", "README.md", "ROADMAP.md", "CHANGES.md")

# where repo CLIs define their flags (scanned for add_argument("--..."))
CLI_SOURCES = ("src/repro/launch", "scripts", "benchmarks", "chip_smoke.py")

# flags the docs quote that belong to third-party tools, not repo CLIs
THIRD_PARTY_FLAGS = {
    "--cov", "--cov-report", "--cov-fail-under",  # pytest-cov
    "--help",  # argparse built-in (never in add_argument calls)
}

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
FLAG_RE = re.compile(r"(?<![\w-])(--[a-z][a-z0-9-]*)")
ADD_ARG_RE = re.compile(r"add_argument\(\s*[\"'](--[a-z][a-z0-9-]*)[\"']")


def doc_files() -> list:
    out = []
    for entry in DOC_GLOBS:
        path = os.path.join(ROOT, entry)
        if os.path.isdir(path):
            out += [os.path.join(path, f) for f in sorted(os.listdir(path))
                    if f.endswith(".md")]
        elif os.path.exists(path):
            out.append(path)
    return out


def known_flags() -> set:
    flags = set(THIRD_PARTY_FLAGS)
    for entry in CLI_SOURCES:
        base = os.path.join(ROOT, entry)
        paths = [base] if os.path.isfile(base) else [
            os.path.join(root, f) for root, _dirs, files in os.walk(base)
            for f in files if f.endswith(".py")]
        for path in paths:
            with open(path) as fh:
                flags.update(ADD_ARG_RE.findall(fh.read()))
    return flags


def check_links(path: str, text: str) -> list:
    errors = []
    for n, line in enumerate(text.splitlines(), 1):
        for target in LINK_RE.findall(line):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            target = target.split("#", 1)[0]  # strip in-file anchors
            if not target:
                continue
            rel = os.path.normpath(
                os.path.join(os.path.dirname(path), target))
            root_rel = os.path.normpath(os.path.join(ROOT, target))
            if not (os.path.exists(rel) or os.path.exists(root_rel)):
                errors.append(f"{os.path.relpath(path, ROOT)}:{n}: "
                              f"broken link -> {target}")
    return errors


def check_flags(path: str, text: str, flags: set) -> list:
    errors = []
    for n, line in enumerate(text.splitlines(), 1):
        for flag in FLAG_RE.findall(line):
            if flag not in flags:
                errors.append(f"{os.path.relpath(path, ROOT)}:{n}: "
                              f"flag {flag} not defined by any repo CLI "
                              f"(launchers/scripts/benchmarks/chip_smoke.py)")
    return errors


def main() -> int:
    flags = known_flags()
    errors = []
    docs = doc_files()
    for path in docs:
        with open(path) as fh:
            text = fh.read()
        errors += check_links(path, text)
        errors += check_flags(path, text, flags)
    if errors:
        print(f"check_docs: {len(errors)} finding(s) in {len(docs)} docs:")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"check_docs OK: {len(docs)} docs, {len(flags)} known flags, "
          f"links + flags clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
