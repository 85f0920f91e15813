"""Trace-site parity lint (pass #5).

The span recorder names its instrumentation points twice — the
``SITES`` catalogue in ``trace/__init__.py`` and the site table in
``docs/TRACING.md`` — and the package's ``trace.span("...")`` /
``trace.event("...")`` / ``trace.add_span("...")`` /
``trace.compile_span("...")`` literals must agree with both.  A span site
present in one layer but not the others is either a timeline name no
dashboard can look up, or a documented signal that never records — the
same silent-drift class the chaos and metrics passes exist for.

Checked equivalences:

* every ``span``/``event``/``add_span`` literal in the package names a
  catalogued site;
* every catalogued site has at least one call site in the package (a
  catalogue entry nothing records is dead);
* the docs/TRACING.md site table is exactly the catalogue (both
  directions).

The names the program gives its DEVICE work are held together the same
way (``DEVICE_SCOPES``, ``DEVICE_SUBSCOPES`` and ``DEVICE_KERNELS`` beside
``SITES``): every ``named_scope("...")`` literal names a catalogued scope
or sub-scope, every
``pallas_call(..., name="...")`` literal a catalogued kernel (and no
kernel of ops/flash_attention.py goes unnamed — an unnamed one reads as
the enclosing jit's name in a capture, the same for every kernel), every
catalogued name has a call site, and docs/TRACING.md's "Device names"
table mirrors both tuples.  A renamed scope would otherwise silently
empty a phase of ``trace/device.py``'s table, a renamed kernel a
per-kernel metric of the benchmark.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Set, Tuple

from ._common import Finding, iter_py_files, read_text

CHECK = "trace"

TRACE_INIT_PY = "horovod_tpu/trace/__init__.py"
TRACING_MD = "docs/TRACING.md"
FLASH_PY = "horovod_tpu/ops/flash_attention.py"

_STR_RE = re.compile(r"\"([a-z0-9_.]+)\"")
# matches trace.span("x") / _trace.event("x") / trace.add_span("x") —
# any alias ending in `trace.`; the method set keeps collective_ops'
# unrelated _span(name, ...) helper out; a bare add_span("x") is the
# recorder's own record, in trace/__init__.py
_CALL_RE = re.compile(
    r"(?:\w*trace\.|(?<![\w.]))(?:span|event|add_span|compile_span)"
    r"\(\s*[\"']([a-z0-9_.]+)[\"']")
_DOC_ROW_RE = re.compile(
    r"^\|\s*`([a-z0-9_]+(?:\.[a-z0-9_]+)+)`\s*\|", re.MULTILINE)
# device names: (catalogue tuple, kind in the docs table, call-site regex)
_SCOPE_RE = re.compile(r"named_scope\(\s*[\"']([a-z0-9_]+)[\"']")
# a pallas_call's name= among its own arguments (one level of nested
# parentheses: functools.partial(...), grid=(...)); an unnamed call
# cannot match into the next one, its closing parenthesis stops it
_KERNEL_RE = re.compile(
    r"pallas_call\((?:[^()]|\([^()]*\))*?\bname\s*=\s*[\"']([a-z0-9_]+)[\"']")
_DEVICE_NAMES = (
    ("DEVICE_SCOPES", "scope", _SCOPE_RE),
    # parts of a phase: the same literal, a tuple and a docs kind of their own
    ("DEVICE_SUBSCOPES", "subscope", _SCOPE_RE),
    ("DEVICE_KERNELS", "kernel", _KERNEL_RE),
)
_DEVICE_ROW_RE = re.compile(
    r"^\|\s*`([a-z0-9_]+)`\s*\|\s*(scope|subscope|kernel)\s*\|",
    re.MULTILINE)


def catalogue(root: str, name: str = "SITES") -> Dict[str, int]:
    """entry -> its line, for the tuple ``name`` of trace/__init__.py."""
    text = read_text(os.path.join(root, TRACE_INIT_PY))
    if text is None:
        return {}
    m = re.search(rf"^{name}\s*=\s*\(", text, re.MULTILINE)
    if not m:
        return {}
    i = text.index("(", m.start())
    depth, j = 0, i
    while j < len(text):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                break
        j += 1
    out: Dict[str, int] = {}
    for sm in _STR_RE.finditer(text, i, j):
        out[sm.group(1)] = text.count("\n", 0, sm.start()) + 1
    return out


def run(root: str) -> List[Finding]:
    findings: List[Finding] = []
    sites = catalogue(root)
    if not sites:
        findings.append(Finding(
            CHECK, TRACE_INIT_PY, 0, "missing",
            "trace/__init__.py SITES catalogue not found/empty — the "
            "span-site registry is gone"))
        return findings

    # -- call sites ----------------------------------------------------------
    used: Set[str] = set()
    sources: Dict[str, str] = {}
    for rel in [*iter_py_files(root,
                               exclude_dirs=("analysis", "trace",
                                             "__pycache__")),
                TRACE_INIT_PY]:
        text = read_text(os.path.join(root, rel))
        if text is None:
            continue
        sources[rel] = text
        for m in _CALL_RE.finditer(text):
            site = m.group(1)
            used.add(site)
            if site not in sites:
                lineno = text.count("\n", 0, m.start()) + 1
                findings.append(Finding(
                    CHECK, rel, lineno, site,
                    f"trace site {site!r} is recorded here but not in "
                    "the trace SITES catalogue — the timeline carries a "
                    "name no site table explains",
                ))

    for site, lineno in sorted(sites.items()):
        if site not in used:
            findings.append(Finding(
                CHECK, TRACE_INIT_PY, lineno, site,
                f"catalogued trace site {site!r} has no span()/event()/"
                "add_span() call site in the package (dead catalogue "
                "entry)",
            ))

    # -- documented table ----------------------------------------------------
    doc_text = read_text(os.path.join(root, TRACING_MD))
    if doc_text is None:
        findings.append(Finding(CHECK, TRACING_MD, 0, "missing",
                                "docs/TRACING.md not found"))
        return findings
    doc_sites: Dict[str, int] = {}
    for m in _DOC_ROW_RE.finditer(doc_text):
        doc_sites[m.group(1)] = doc_text.count("\n", 0, m.start()) + 1
    for site, lineno in sorted(sites.items()):
        if site not in doc_sites:
            findings.append(Finding(
                CHECK, TRACE_INIT_PY, lineno, site,
                f"trace site {site!r} is catalogued but missing from "
                "the docs/TRACING.md site table",
            ))
    for site, lineno in sorted(doc_sites.items()):
        if site not in sites:
            findings.append(Finding(
                CHECK, TRACING_MD, lineno, site,
                f"docs/TRACING.md documents trace site {site!r} but the "
                "SITES catalogue does not contain it",
            ))
    findings.extend(_device_names(root, sources, doc_text))
    return findings


def _device_names(root: str, sources: Dict[str, str],
                  doc_text: str) -> List[Finding]:
    """DEVICE_SCOPES / DEVICE_KERNELS against their call sites in
    ``sources`` (path -> text of the package's files) and the docs'
    "Device names" table.  A tree whose catalogue has neither tuple
    (before PR 24) has nothing to hold together."""
    findings: List[Finding] = []
    doc_rows: Dict[Tuple[str, str], int] = {}
    for m in _DEVICE_ROW_RE.finditer(doc_text):
        doc_rows[(m.group(1), m.group(2))] = (
            doc_text.count("\n", 0, m.start()) + 1)

    for tuple_name, kind, call_re in _DEVICE_NAMES:
        names = catalogue(root, tuple_name)
        # a literal is catalogued if ANY tuple read by the same pattern
        # holds it (a named_scope is a phase or a part of one)
        siblings: Set[str] = set()
        for other, _, other_re in _DEVICE_NAMES:
            if other_re is call_re and other != tuple_name:
                siblings |= set(catalogue(root, other))
        used: Set[str] = set()
        for rel, text in sources.items():
            for m in call_re.finditer(text):
                used.add(m.group(1))
                if m.group(1) not in names and m.group(1) not in siblings:
                    findings.append(Finding(
                        CHECK, rel, text.count("\n", 0, m.start()) + 1,
                        m.group(1),
                        f"device {kind} {m.group(1)!r} is named here but "
                        f"not in the trace {tuple_name} catalogue — a "
                        "capture carries a name no table explains",
                    ))
        for name, lineno in sorted(names.items()):
            if name not in used:
                findings.append(Finding(
                    CHECK, TRACE_INIT_PY, lineno, name,
                    f"catalogued device {kind} {name!r} has no call site "
                    "in the package (dead catalogue entry)",
                ))
            if (name, kind) not in doc_rows:
                findings.append(Finding(
                    CHECK, TRACE_INIT_PY, lineno, name,
                    f"device {kind} {name!r} is catalogued but missing "
                    "from the docs/TRACING.md \"Device names\" table",
                ))
        for (name, row_kind), lineno in sorted(doc_rows.items()):
            if row_kind == kind and name not in names:
                findings.append(Finding(
                    CHECK, TRACING_MD, lineno, name,
                    f"docs/TRACING.md documents device {kind} {name!r} "
                    f"but {tuple_name} does not contain it",
                ))

    flash = sources.get(FLASH_PY)
    if flash is not None and catalogue(root, "DEVICE_KERNELS"):
        unnamed = (len(re.findall(r"pallas_call\(", flash))
                   - len(_KERNEL_RE.findall(flash)))
        if unnamed:
            findings.append(Finding(
                CHECK, FLASH_PY, 0, "pallas_call",
                f"{unnamed} pallas_call(s) of ops/flash_attention.py "
                "carry no name= — in a capture they read as the "
                "enclosing jit's name, one name for every kernel",
            ))
    return findings
