"""Spec-source extraction pass for the mirror-parity rules (SP01–SP03).

The fast paths reimplement spec functions (``stf/engine.py``'s block
operations, the epoch kernels, the builder's sanctioned substitutions);
``mirror_registry.py`` pins each mirror to the SHA-256 of its spec twin's
source *as compiled* into ``consensus_specs_tpu/specs/``.  This module is
the extraction half: given the spec source texts, it resolves the
**effective definition** of every top-level spec function per fork
(``get_spec`` execs fork sources over one shared globals dict, so the
latest fork in the chain that defines a name wins) and derives, for each
(fork, function):

* an **AST-normalized digest** — the function is re-parsed, its docstring
  dropped, and a schema-independent dump (``_canonical``) hashed, so
  comment/whitespace/docstring churn never fires SP01 while any semantic
  edit does, on any interpreter;
* the ordered **raise sites** (``assert``/``raise`` statements) with a
  digest over their normalized conditions — SP03's audit unit;
* the bare-name **call targets** — spec sources call globals directly, so
  this is exactly the intra-spec call graph SP02 walks from the fast-path
  entry points.

Extraction never imports the jax-heavy package: the mainline fork ladder
is redeclared here and ``tests/analysis/test_mirror_registry.py`` pins it
AST-for-AST against ``specs/builder.py``'s ``FORK_PARENTS``.
"""
from __future__ import annotations

import ast
import hashlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

# Mainline fork ladder as compiled by specs/builder.py (FORK_PARENTS /
# FORK_ORDER).  Experimental forks (eip4844, sharding, ...) carry no fast
# path and are out of scope until FAST_FORKS names one.
FORK_CHAINS: Dict[str, Tuple[str, ...]] = {
    "phase0": ("phase0",),
    "altair": ("phase0", "altair"),
    "bellatrix": ("phase0", "altair", "bellatrix"),
    "capella": ("phase0", "altair", "bellatrix", "capella"),
}

SPEC_SRC_DIR = "consensus_specs_tpu/specs/src"

# Pseudo-forks: spec-shaped reference sources outside the fork ladder a
# mirror may pin against ("ssz" = the merkle-proof reference that
# query/streamproof.py's build_proof twin reimplements byte-for-byte).
EXTRA_SOURCES: Dict[str, str] = {
    "ssz": "consensus_specs_tpu/ssz/gindex.py",
}


def fork_display(fork: str) -> str:
    """Display path of the source file one fork (or pseudo-fork) execs."""
    if fork in EXTRA_SOURCES:
        return EXTRA_SOURCES[fork]
    return f"{SPEC_SRC_DIR}/{fork}.py"


def spec_source_displays() -> Tuple[str, ...]:
    """Every display path the extraction pass reads."""
    seen: List[str] = []
    for chain in FORK_CHAINS.values():
        for f in chain:
            d = fork_display(f)
            if d not in seen:
                seen.append(d)
    seen.extend(EXTRA_SOURCES.values())
    return tuple(seen)


@dataclass(frozen=True)
class RaiseSite:
    """One ``assert``/``raise`` statement inside a spec function."""

    line: int
    kind: str      # "assert" | "raise"
    detail: str    # normalized AST dump of the condition/exception
    source: str    # stripped first source line, for messages


@dataclass(frozen=True)
class SpecFunction:
    """The effective definition of one spec function for one fork."""

    name: str
    fork: str                        # fork whose source file defines it
    src: str                         # display path of the defining file
    line: int
    digest: str                      # AST-normalized source digest
    raise_count: int
    raise_digest: str
    raise_sites: Tuple[RaiseSite, ...]
    calls: Tuple[str, ...]           # bare-name call targets, sorted


class SpecSnapshot:
    """Effective spec-function definitions per fork, plus per-fork digests
    (the ANALYSIS.json ``spec_snapshot`` rows)."""

    def __init__(self, forks: Dict[str, Dict[str, SpecFunction]],
                 missing: Tuple[str, ...]):
        self.forks = forks
        self.missing = missing        # displays whose text was unavailable
        self.fork_digests: Dict[str, str] = {}
        for fork, defs in forks.items():
            h = hashlib.sha256()
            for name in sorted(defs):
                h.update(name.encode())
                h.update(defs[name].digest.encode())
            self.fork_digests[fork] = h.hexdigest()

    def get(self, fork: str, name: str) -> Optional[SpecFunction]:
        return self.forks.get(fork, {}).get(name)


def _strip_docstring(node: ast.FunctionDef) -> ast.FunctionDef:
    body = node.body
    if (body and isinstance(body[0], ast.Expr)
            and isinstance(body[0].value, ast.Constant)
            and isinstance(body[0].value.value, str)):
        body = body[1:] or [ast.Pass()]
    clone = ast.FunctionDef(
        name=node.name, args=node.args, body=body,
        decorator_list=node.decorator_list, returns=node.returns,
        type_comment=None)
    return clone


def _canonical(node) -> str:
    """A dump of ``node`` that no interpreter's AST schema changes: fields
    are named, and fields left None or empty are omitted, so a field that
    a newer Python adds (3.12's ``type_params``) digests like its absence.
    ``ast.dump`` lists every field of the running interpreter's schema."""
    if isinstance(node, ast.AST):
        parts = []
        for name in node._fields:
            value = getattr(node, name, None)
            if value is None or value == []:
                continue
            parts.append(f"{name}={_canonical(value)}")
        return f"{type(node).__name__}({', '.join(parts)})"
    if isinstance(node, list):
        return f"[{', '.join(_canonical(v) for v in node)}]"
    return repr(node)


def _function_facts(node: ast.FunctionDef, fork: str, src: str,
                    lines: List[str]) -> SpecFunction:
    digest = hashlib.sha256(
        _canonical(_strip_docstring(node)).encode()).hexdigest()

    sites: List[RaiseSite] = []
    calls: set = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Assert):
            detail = "assert " + _canonical(sub.test)
            if sub.msg is not None:
                detail += ", " + _canonical(sub.msg)
            sites.append(RaiseSite(sub.lineno, "assert", detail,
                                   _src_line(lines, sub.lineno)))
        elif isinstance(sub, ast.Raise):
            detail = "raise " + (
                _canonical(sub.exc) if sub.exc else "")
            sites.append(RaiseSite(sub.lineno, "raise", detail,
                                   _src_line(lines, sub.lineno)))
        elif isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name):
            calls.add(sub.func.id)
    sites.sort(key=lambda s: s.line)
    rh = hashlib.sha256()
    for s in sites:
        rh.update(s.detail.encode())
    return SpecFunction(
        name=node.name, fork=fork, src=src, line=node.lineno, digest=digest,
        raise_count=len(sites), raise_digest=rh.hexdigest(),
        raise_sites=tuple(sites), calls=tuple(sorted(calls)))


def _src_line(lines: List[str], lineno: int) -> str:
    if 1 <= lineno <= len(lines):
        return lines[lineno - 1].strip()
    return ""


# per-file extraction memo: override runs re-parse one file, not five
_FILE_MEMO: Dict[Tuple[str, str, str], Optional[Dict[str, SpecFunction]]] = {}
_SNAP_MEMO: Dict[Tuple, SpecSnapshot] = {}


def _extract_file(fork: str, display: str,
                  text: str) -> Optional[Dict[str, SpecFunction]]:
    """Top-level function facts of one spec source (None on syntax error)."""
    key = (fork, display,
           hashlib.sha256(text.encode("utf-8", "surrogatepass")).hexdigest())
    if key in _FILE_MEMO:
        return _FILE_MEMO[key]
    if len(_FILE_MEMO) > 64:
        _FILE_MEMO.clear()
    try:
        tree = ast.parse(text)
    except SyntaxError:
        _FILE_MEMO[key] = None
        return None
    lines = text.splitlines()
    defs = {node.name: _function_facts(node, fork, display, lines)
            for node in tree.body if isinstance(node, ast.FunctionDef)}
    _FILE_MEMO[key] = defs
    return defs


def snapshot(texts: Dict[str, Optional[str]]) -> SpecSnapshot:
    """Build the per-fork effective-definition snapshot from spec texts
    (``{display: source}`` — the runner feeds it entry texts so override
    runs see mutated spec sources, never the disk)."""
    memo_key = tuple(sorted(
        (d, hashlib.sha256(t.encode("utf-8", "surrogatepass")).hexdigest())
        for d, t in texts.items() if t is not None))
    cached = _SNAP_MEMO.get(memo_key)
    if cached is not None:
        return cached
    if len(_SNAP_MEMO) > 16:
        _SNAP_MEMO.clear()

    missing: List[str] = []
    per_file: Dict[Tuple[str, str], Optional[Dict[str, SpecFunction]]] = {}

    def file_defs(fork: str) -> Dict[str, SpecFunction]:
        display = fork_display(fork)
        key = (fork, display)
        if key not in per_file:
            text = texts.get(display)
            if text is None:
                if display not in missing:
                    missing.append(display)
                per_file[key] = {}
            else:
                per_file[key] = _extract_file(fork, display, text) or {}
        return per_file[key]

    forks: Dict[str, Dict[str, SpecFunction]] = {}
    for fork, chain in FORK_CHAINS.items():
        effective: Dict[str, SpecFunction] = {}
        for layer in chain:
            effective.update(file_defs(layer))
        forks[fork] = effective
    for pseudo in EXTRA_SOURCES:
        forks[pseudo] = dict(file_defs(pseudo))

    snap = SpecSnapshot(forks, tuple(missing))
    _SNAP_MEMO[memo_key] = snap
    return snap


def reachable(snap: SpecSnapshot, fork: str,
              entries: Tuple[str, ...]) -> Dict[str, SpecFunction]:
    """Spec functions reachable from ``entries`` over the fork's
    intra-spec call graph (bare-name calls, shared-globals dispatch)."""
    defs = snap.forks.get(fork, {})
    seen: Dict[str, SpecFunction] = {}
    stack = [e for e in entries if e in defs]
    while stack:
        name = stack.pop()
        if name in seen:
            continue
        fn = defs[name]
        seen[name] = fn
        for callee in fn.calls:
            if callee in defs and callee not in seen:
                stack.append(callee)
    return seen
