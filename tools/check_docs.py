"""Link-and-reference checker for the docs layer.

Docs rot in three characteristic ways, each checked here against the
tree as it actually is:

  links  — every relative markdown link in docs/*.md and README.md
           must resolve to a real file (anchors are stripped; absolute
           URLs are ignored);
  paths  — every repo path a doc names in prose or code spans
           (src/repro/..., tests/..., benchmarks/..., examples/...,
           tools/..., docs/...) must exist;
  flags  — every ``--flag`` a doc names must be a real flag of
           examples/serve_batch.py or chip_smoke.py (parsed from their
           add_argument calls) or one of the few known non-argparse
           flags.

Run: python tools/check_docs.py          (from the repo root or not —
the repo root is located relative to this file). Exit 0 = docs clean;
1 = each violation printed with file and rule. CI runs this in the
lint job so a renamed module, a dropped flag, or a moved doc fails the
build instead of silently orphaning the docs.
"""

from __future__ import annotations

import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

DOC_FILES = sorted(REPO.glob("docs/*.md")) + [REPO / "README.md"]

# [text](target) — markdown links, including images
LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
# repo-relative file references named in prose/code spans
PATH_RE = re.compile(
    r"\b(?:src/repro|tests|benchmarks|examples|tools|docs)"
    r"(?:/[\w.-]+)*/[\w.-]+\.(?:py|md|json|yml)\b"
)
FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9]*(?:-[a-z0-9]+)*\b")
ARG_RE = re.compile(r"add_argument\(\s*[\"'](--[a-z0-9-]+)[\"']")

# flags that are real but not serve_batch argparse flags
KNOWN_FLAGS = {
    "--json",    # benchmarks/run.py output switch (hand-parsed)
    "--check",   # ruff format --check (the CI lint invocation)
}


def serve_flags() -> set[str]:
    flags = set(KNOWN_FLAGS)
    for script in (REPO / "examples" / "serve_batch.py",
                   REPO / "chip_smoke.py"):
        flags |= set(ARG_RE.findall(script.read_text()))
    return flags


def check_file(path: pathlib.Path, flags: set[str]) -> list[str]:
    errors = []
    rel = path.relative_to(REPO)
    text = path.read_text()

    for target in LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        plain = target.split("#", 1)[0]
        if not plain:        # pure in-page anchor
            continue
        if not (path.parent / plain).exists():
            errors.append(f"{rel}: broken link -> {target}")

    for ref in sorted(set(PATH_RE.findall(text))):
        if not (REPO / ref).exists():
            errors.append(f"{rel}: referenced path does not exist: {ref}")

    for flag in sorted(set(FLAG_RE.findall(text))):
        if flag not in flags:
            errors.append(
                f"{rel}: names flag {flag}, which neither examples/"
                f"serve_batch.py nor chip_smoke.py defines"
            )
    return errors


def main() -> int:
    flags = serve_flags()
    errors = []
    for path in DOC_FILES:
        if path.exists():
            errors.extend(check_file(path, flags))
        else:
            errors.append(f"expected doc file missing: "
                          f"{path.relative_to(REPO)}")
    for e in errors:
        print(f"check_docs: {e}", file=sys.stderr)
    if not errors:
        n = sum(1 for p in DOC_FILES if p.exists())
        print(f"check_docs: {n} files, all links/paths/flags resolve")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
