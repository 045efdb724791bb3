"""Device time of a compiled program by the program's named scopes.

The program names its regions with ``jax.named_scope`` (``mla``,
``moe_route``, ...).  XLA keeps the name in each instruction's
``op_name`` metadata, with the transformations around it
(``transpose(jvp(moe_experts))`` in a backward pass).  The TPU trace's
``XLA Ops`` events carry the instruction's text without that metadata
(``%fusion.12 = bf16[...] fusion(...), kind=kLoop, calls=...``), and
``trace.py`` keeps op names only, so the attribution goes through the
compiled program's HLO text: an op event's instruction name is looked up
in the text, and the op is given the innermost of the wanted scopes that
its ``op_name`` holds.  A fusion with no metadata of its own takes its
fused computation's root's.

Ops of one program are told from another's by time: an op belongs to the
program whose run (``XLA Modules``) holds its start.
"""
from __future__ import annotations

import bisect
import re
from functools import lru_cache
from typing import Dict, Iterable, Optional, Tuple

from benchmarks.chip import trace as tr

#: the program's named scopes (``repro.models.transformer`` and
#: ``repro.models.moe``); an op goes to the innermost of them it is in
MODEL_SCOPES = ("mla", "moe_route", "moe_dispatch", "moe_experts",
                "moe_shared", "moe_combine")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` (or ``fusion.12``) ->
    ``fusion.12``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.strip().lstrip("%")


def _scope_of(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The innermost of ``scopes`` named as a path component of
    ``op_name``, transformations such as ``jvp(...)`` seen through."""
    best, at = None, -1
    for s in scopes:
        for m in re.finditer(r"(?:^|[/(])" + re.escape(s) + r"(?=[)/]|$)",
                             op_name):
            if m.start() > at:
                best, at = s, m.start()
    return best


@lru_cache(maxsize=4)
def instruction_scopes(hlo_text: str, scopes: Tuple[str, ...]
                       ) -> Dict[str, str]:
    """Instruction name -> scope for every instruction of the text whose
    ``op_name`` (or, for a fusion without one, its fused root's) holds
    one of ``scopes``.  Kept for the readers of one window, which share
    a program's text."""
    op_names: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    computation = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and "=" not in line.split("{")[0]:
            computation = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        if line.lstrip().startswith("ROOT") and computation:
            roots[computation] = name
        o = _OP_NAME.search(line)
        if o:
            op_names[name] = o.group(1)
        k = _CALLS.search(line)
        if k:
            calls[name] = k.group(1)
    out = {}
    for name in set(op_names) | set(calls):
        op = op_names.get(name)
        if op is None and calls.get(name) in roots:
            op = op_names.get(roots[calls[name]])
        s = _scope_of(op, scopes) if op else None
        if s:
            out[name] = s
    return out


def scope_seconds(trace: tr.Trace, module: str, hlo_text: str,
                  scopes: Iterable[str]) -> Dict[str, float]:
    """Device seconds of each of ``scopes`` inside the window's runs of
    ``module``, summed over its ops (every chip of the trace)."""
    scopes = tuple(scopes)
    runs = sorted(tr.module_runs(trace, module))
    where = instruction_scopes(hlo_text, scopes)
    out = {s: 0.0 for s in scopes}
    if not runs or not where:
        return out
    starts = [s for s, _ in runs]
    for key, spans in trace.ops.items():
        scope = where.get(instruction_name(key.split("|", 1)[-1]))
        if scope is None:
            continue
        for iv in spans:
            i = bisect.bisect_right(starts, iv[0]) - 1
            if i < 0 or iv[0] >= runs[i][1]:
                continue
            c = tr.clip(iv, trace.window)
            if c:
                out[scope] += (c[1] - c[0]) / 1e9
    return out


def ms_per_run(obs, module: str, hlo_text: Optional[str],
               scopes: Iterable[str]) -> Optional[float]:
    """Device ms in ``scopes`` (of :data:`MODEL_SCOPES`) together per run
    of ``module`` in the window; None without a trace, the program's
    text, a run, or an op in any of the scopes."""
    if obs.trace is None or not hlo_text:
        return None
    n = len(tr.module_runs(obs.trace, module))
    if not n:
        return None
    secs = scope_seconds(obs.trace, module, hlo_text, MODEL_SCOPES)
    total = sum(secs[s] for s in scopes)
    return 1e3 * total / n if total > 0 else None
