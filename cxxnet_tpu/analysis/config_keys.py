"""Config-key drift: code vs. doc tables (rule ``config-key-drift``).

``main.py``/``wrapper.py`` (and, since the sequence layers, ``layers/
sequence.py`` and ``io/iter_tokens.py``, documented in ``doc/sequence.md``)
parse their config keys through two idioms —
the ``simple`` string-key dispatch table inside ``set_param`` and
``name == '<key>'`` section-marker comparisons.  Both are extracted
statically here and cross-checked against the key tables in
``doc/tasks.md`` / ``doc/io.md`` / ``doc/trainer.md``: a key the CLI
parses but no doc table mentions is drift and fails the lint.  This
generalizes PR 7's one-off fallback-matrix drift test; the markdown
table helpers below are the shared extractor that test (and any future
doc-drift consumer) uses — one extractor, many consumers.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding, Module, Repo

RULES = ('config-key-drift',)

#: config-parsing sources and the doc files whose tables document them
KEY_SOURCES = ('cxxnet_tpu/main.py', 'cxxnet_tpu/wrapper.py',
               'cxxnet_tpu/layers/sequence.py', 'cxxnet_tpu/io/iter_tokens.py')
DOC_FILES = ('doc/tasks.md', 'doc/io.md', 'doc/trainer.md',
             'doc/sequence.md')

_KEY_RE = re.compile(r'^[a-z_][a-z0-9_]*(\.[a-z_][a-z0-9_]*)*$')

#: backtick span opening with a config-key-shaped token, optionally
#: followed by `= value` (the doc tables write both `key` and `key = v`)
_DOC_KEY_RE = re.compile(r'`([a-zA-Z_][a-zA-Z0-9_.]*)\s*(?:=[^`]*)?`')


# --- code side --------------------------------------------------------------

def parsed_keys(mod: Module) -> Dict[str, int]:
    """Config keys the module parses -> first line seen.

    Sources: (a) string keys of dict literals inside any ``set_param``
    function (the CLI's ``simple`` dispatch table), (b) constants
    compared against a variable named ``name`` anywhere in the module
    (the section-marker idiom ``if name == 'data':``)."""
    keys: Dict[str, int] = {}

    def note(key: str, line: int) -> None:
        if _KEY_RE.match(key):
            keys.setdefault(key, line)

    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == 'set_param':
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    const = [k for k in sub.keys
                             if isinstance(k, ast.Constant)
                             and isinstance(k.value, str)]
                    if len(const) == len(sub.keys) and const:
                        for k in const:
                            note(k.value, k.lineno)
        if isinstance(node, ast.Compare):
            left = node.left
            if isinstance(left, ast.Name) and left.id == 'name':
                for op, comp in zip(node.ops, node.comparators):
                    if not isinstance(op, (ast.Eq, ast.In)):
                        continue
                    if isinstance(comp, ast.Constant) \
                            and isinstance(comp.value, str):
                        note(comp.value, comp.lineno)
                    elif isinstance(comp, (ast.Tuple, ast.List, ast.Set)):
                        for el in comp.elts:
                            if isinstance(el, ast.Constant) \
                                    and isinstance(el.value, str):
                                note(el.value, el.lineno)
    return keys


# --- doc side (the shared extractor) ----------------------------------------

def doc_keys(text: str) -> set:
    """Every config-key-shaped backtick token in a markdown file —
    table cells and inline prose both count as documentation."""
    return {m.group(1) for m in _DOC_KEY_RE.finditer(text)}


def doc_table_rows(text: str, after: Optional[str] = None
                   ) -> List[Tuple[str, ...]]:
    """Markdown table rows as tuples of stripped cell strings,
    excluding header-separator rows (``|---|---|``).  ``after`` (a
    heading substring) restricts parsing to everything past its first
    occurrence — the "last table in the section" idiom the demotion-
    matrix drift test relies on."""
    if after is not None:
        _, _, text = text.partition(after)
    rows: List[Tuple[str, ...]] = []
    for line in text.splitlines():
        line = line.strip()
        if not (line.startswith('|') and line.endswith('|')):
            continue
        cells = tuple(c.strip() for c in line[1:-1].split('|'))
        if all(set(c) <= set('-: ') for c in cells):
            continue
        rows.append(cells)
    return rows


def backtick_key(cell: str) -> Optional[str]:
    """The leading backticked key of a table cell — accepts both the
    bare ``key`` and ``key = v`` spellings; None for prose/header
    cells."""
    m = _DOC_KEY_RE.match(cell.strip())
    return m.group(1) if m else None


def documented_keys(repo: Repo,
                    doc_files: Sequence[str] = DOC_FILES) -> set:
    out: set = set()
    for rel in doc_files:
        if repo.has(rel):
            out |= doc_keys(repo.read_text(rel))
    return out


# --- the checker ------------------------------------------------------------

def check_module(mod: Module, documented: set,
                 doc_files: Sequence[str] = DOC_FILES) -> List[Finding]:
    findings: List[Finding] = []
    docs = ', '.join(os.path.basename(d) for d in doc_files)
    for key, line in sorted(parsed_keys(mod).items()):
        if key in documented:
            continue
        findings.append(Finding(
            'config-key-drift', mod.rel, line,
            f'config key {key!r} is parsed here but documented in none '
            f'of the key tables ({docs}) — add a doc row or drop the '
            f'key'))
    return findings


def run(repo: Repo) -> List[Finding]:
    documented = documented_keys(repo)
    findings: List[Finding] = []
    for rel in KEY_SOURCES:
        if repo.has(rel):
            findings.extend(check_module(repo.module(rel), documented))
    return findings
