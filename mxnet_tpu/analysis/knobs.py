"""Machine-readable view of the MXNET_* environment-knob registry.

The single source of truth stays ``base.declare_env`` — every knob the
framework consults is declared there with a type, default and doc
string, and ``base.env`` resolves reads through it.  This module is the
analysis-facing projection: a typed :class:`Knob` table for tooling,
the generated markdown table that docs/ROBUSTNESS.md folds in (between
the ``knob-table`` markers), and the drift check the ``env-knob`` lint
rule runs in package mode.  Two registries would immediately drift
against each other; a projection cannot.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DOCS_BEGIN = "<!-- knob-table:begin (generated:"
DOCS_END = "<!-- knob-table:end -->"


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str
    default: object
    doc: str


def registry() -> Dict[str, Knob]:
    """Every declared knob, keyed by name (from base._ENV_FLAGS)."""
    from ..base import list_env_flags
    out = {}
    for name, (typ, default, doc) in sorted(list_env_flags().items()):
        out[name] = Knob(name=name, type=typ.__name__, default=default,
                         doc=" ".join(doc.split()))
    return out


def markdown_table() -> str:
    """The knob table docs/ROBUSTNESS.md folds in (regenerate with
    ``python -m mxnet_tpu.analysis --knob-table``)."""
    lines = [
        DOCS_BEGIN + " python -m mxnet_tpu.analysis --knob-table) -->",
        "| knob | type | default | what it does |",
        "|------|------|---------|--------------|",
    ]
    for knob in registry().values():
        lines.append("| `%s` | %s | `%r` | %s |" % (
            knob.name, knob.type, knob.default, knob.doc or "—"))
    lines.append(DOCS_END)
    return "\n".join(lines)


def missing_in_text(text: str) -> List[str]:
    """Registered knobs absent from ``text``.  Matches the
    backtick-delimited form (`` `NAME` ``) the table and every doc
    mention use — a bare substring test would let a knob that is a
    PREFIX of another (RETRY_MAX vs RETRY_MAX_MS) pass on the longer
    name's row alone."""
    return [name for name in registry()
            if ("`%s`" % name) not in text]


def check_drift(package_root: Path) -> Optional[str]:
    """Stale-table drift check (``--check``): the generated knob table
    must appear VERBATIM between docs/ROBUSTNESS.md's markers — a knob
    added/retyped/redocumented without regenerating the table is a CI
    failure, not a silent regeneration.  None when in sync (or no docs
    checkout).  ``package_root`` locates the docs checkout only: the
    registry itself is runtime state of the IMPORTED package
    (base.declare_env), so this check is meaningful for the live tree,
    not an arbitrary other checkout."""
    docs_path = Path(package_root).resolve().parent / "docs" \
        / "ROBUSTNESS.md"
    if not docs_path.exists():
        if not docs_path.parent.exists():
            return None   # installed package without a docs checkout
        return ("docs/ROBUSTNESS.md does not exist but docs/ does: "
                "the knob table (`python -m mxnet_tpu.analysis "
                "--knob-table`) must live there")
    if markdown_table() not in docs_path.read_text():
        return ("docs/ROBUSTNESS.md knob table is STALE: regenerate "
                "with `python -m mxnet_tpu.analysis --knob-table` and "
                "paste it over the knob-table:begin/end block")
    return None


def docs_missing(package_root: Path) -> Tuple[List[str], Path]:
    """Registered knobs absent from docs/ROBUSTNESS.md.

    Returns ``(missing_names, docs_path)``; an empty list when the docs
    file does not exist (installed package, no repo checkout)."""
    docs_path = Path(package_root).resolve().parent / "docs" \
        / "ROBUSTNESS.md"
    if not docs_path.exists():
        return [], docs_path
    return missing_in_text(docs_path.read_text()), docs_path
