"""Env-knob hygiene check: every ``DYN_*`` environment variable the project
reads must appear in a docs env table, and every ``DYN_*`` name the docs
mention must actually exist — either as a literal the source reads or as a
config-cascade name auto-generated from a ``config.py`` settings dataclass
(``DYN_{SECTION}_{FIELD}``).

Knobs rot in both directions: a knob added in code but never documented is
undiscoverable (operators grep the docs, not the source), and a knob renamed
in code but not in the docs silently stops working for everyone following
the docs. This gate makes the docs env tables the enforced registry of both
sets. Dynamic prefix families (``DYN_SVC_<SERVICE>_<FIELD>`` from the SDK's
service-config cascade) are validated by prefix — the source reads the
prefix, the docs may enumerate concrete instances.

Run directly (``python tools/check_env_knobs.py``) or via the test suite
(``tests/test_observability.py``).
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
import sys

_KNOB = re.compile(r"DYN_[A-Z0-9_]+")
_QUOTED_KNOB = re.compile(r"[\"'](DYN_[A-Z0-9_]*)[\"']")

#: Source files scanned for knob literals: the package, the top-level bench
#: harness (its BENCH_* knobs are out of scope; its DYN_* reads are not),
#: and the operator tools.
_SOURCE_GLOBS = [("dynamo_tpu", "**/*.py"), (".", "bench.py"), ("tools", "*.py")]
#: Docs scanned for the documented set — every env table the project keeps.
_DOC_GLOBS = [("docs", "*.md"), (".", "README.md")]


def _repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parent.parent


def source_knobs(root: pathlib.Path | None = None) -> tuple[set[str], set[str]]:
    """(exact knob names, dynamic prefixes) read as string literals.

    A quoted literal ending in ``_`` (e.g. ``"DYN_SVC_"``) is a prefix the
    code composes names under, not a knob itself.
    """
    root = root or _repo_root()
    exact: set[str] = set()
    prefixes: set[str] = set()
    for base, glob in _SOURCE_GLOBS:
        for path in sorted((root / base).glob(glob)):
            for name in _QUOTED_KNOB.findall(path.read_text()):
                (prefixes if name.endswith("_") else exact).add(name)
    return exact, prefixes


def generated_knobs() -> set[str]:
    """``DYN_{SECTION}_{FIELD}`` names the config cascade accepts, derived
    from every ``*Settings`` dataclass in ``dynamo_tpu.config`` (section =
    snake_case of the class name minus the suffix — the same derivation the
    ``load_*_settings`` helpers hardcode)."""
    from dynamo_tpu import config

    knobs: set[str] = set()
    for attr in vars(config).values():
        if not (isinstance(attr, type) and dataclasses.is_dataclass(attr)
                and attr.__name__.endswith("Settings")):
            continue
        stem = attr.__name__[: -len("Settings")]
        section = re.sub(r"(?<!^)(?=[A-Z])", "_", stem).upper()
        for f in dataclasses.fields(attr):
            knobs.add(f"DYN_{section}_{f.name.upper()}")
    return knobs


def documented_knobs(root: pathlib.Path | None = None) -> set[str]:
    """Every full ``DYN_*`` name the docs mention. Wildcard/prefix mentions
    (``DYN_TENANT_*`` captures as ``DYN_TENANT_``) are dropped — a family
    mention documents nothing enumerable."""
    root = root or _repo_root()
    out: set[str] = set()
    for base, glob in _DOC_GLOBS:
        for path in sorted((root / base).glob(glob)):
            out.update(n for n in _KNOB.findall(path.read_text()) if not n.endswith("_"))
    return out


def check(source: set[str], generated: set[str], prefixes: set[str],
          documented: set[str]) -> list[str]:
    problems: list[str] = []
    known = source | generated
    for name in sorted(known - documented):
        problems.append(f"{name} is read by the source but appears in no docs env table")
    for name in sorted(documented - known):
        if any(name.startswith(p) for p in prefixes):
            continue  # concrete instance of a dynamic family (DYN_SVC_...)
        problems.append(f"{name} is documented but nothing reads it (renamed or removed?)")
    return problems


def main() -> int:
    source, prefixes = source_knobs()
    generated = generated_knobs()
    documented = documented_knobs()
    problems = check(source, generated, prefixes, documented)
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print(
        f"ok: {len(source | generated)} DYN_* knobs "
        f"({len(source)} literal, {len(generated - source)} config-generated, "
        f"{len(prefixes)} dynamic prefixes) all documented; "
        f"{len(documented)} documented names all live"
    )
    return 0


if __name__ == "__main__":
    # Direct CLI use from a checkout: make the repo importable.
    sys.path.insert(0, str(_repo_root()))
    sys.exit(main())
