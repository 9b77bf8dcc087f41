"""Split a program's device time by the named scopes of its ops.

The train step opens three top-level ``jax.named_scope``s, ``trunk``,
``head`` (with ``head/sample`` inside) and ``optimizer``. A scope is not in
the device trace: an ``XLA Ops`` event carries the instruction's short name
(``fusion.634``) and its timing only. It is in the compiled module, whose
instructions carry ``metadata={op_name="jit(f)/jvp(trunk)/..."}``; the
trace's short names are the instruction names of that module. So:

- ``op_scopes(hlo_text)`` maps each instruction name to its scope path,
  with the program's own ``jit(...)`` dropped and the ``jvp(...)``,
  ``transpose(...)`` and ``vmap(...)`` wrappers taken off, so that
  ``transpose(jvp(trunk))/mul`` reads ``trunk/mul``. A fusion carries
  the metadata the compiler gave it (an output fusion's is its matmul's),
  or else is attributed by its fused computation's root; an instruction
  the compiler added without metadata by the values it moves;
- ``mixed_fusions(hlo_text)`` names the fusions whose fused instructions
  lie under more than one top-level scope;
- ``split`` sums one chip's exclusive op time per scope and divides it by
  the program's runs; ``read`` does so for the ops of a ``.xplane.pb``
  that start inside a module's ``XLA Modules`` events, averaged over
  chips.

An op whose name is not in the map, or whose path lies under none of the
top-level scopes, counts as ``unscoped``. ``host_spans`` reads a program
span's durations from the same trace, on the device's clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, FrozenSet, Iterable, List, Tuple

from chipbench.devtrace import (DEVICE_PREFIX, exclusive_times,
                                module_name, short_op)

TOP = ("trunk", "head", "optimizer")
SAMPLE = "head/sample"
UNSCOPED = "unscoped"
MIXED = "mixed"                 # time in fusions that span scopes

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION = re.compile(r" fusion\(.*calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def scope_path(op_name: str) -> str:
    """'jit(step)/transpose(jvp(trunk))/mul' -> 'trunk/mul'. '' for a name
    outside any ``jit(...)`` (a parameter's argument path, a reducer)."""
    parts = op_name.split("/")
    if len(parts) < 2 or not parts[0].startswith(("jit(", "pjit(")):
        return ""
    out = []
    for part in parts[1:]:
        m = _TRANSFORM.match(part)
        while m:
            part = m.group(1)
            m = _TRANSFORM.match(part)
        out.append(part)
    return "/".join(out)


def top_scope(path: str) -> str:
    first = path.split("/", 1)[0]
    return first if first in TOP else UNSCOPED


def _computations(hlo_text: str) -> Dict[str, List[Tuple[str, bool, str]]]:
    """Computation name -> its instructions (name, is root, line)."""
    comps: Dict[str, List[Tuple[str, bool, str]]] = {}
    body = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split("(", 1)[0].split()
            body = (comps.setdefault(head[-1].lstrip("%"), []) if head
                    else None)
            continue
        m = _INSTR.match(line)
        if m and body is not None:
            body.append((m.group(2), bool(m.group(1)), line))
    return comps


def _op_name(line: str) -> str:
    m = _OP_NAME.search(line)
    return scope_path(m.group(1)) if m else ""


def _inherit(start: str, edges: Dict[str, List[str]],
             own: Dict[str, str]) -> str:
    """The path of the nearest instruction along ``edges`` that has one."""
    seen, todo = {start}, list(edges.get(start, ()))
    while todo:
        nxt = []
        for n in todo:
            if own.get(n):
                return own[n]
            if n not in seen:
                seen.add(n)
                nxt.extend(edges.get(n, ()))
        todo = nxt
    return ""


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path ('' where none can be found).

    An instruction takes its own metadata's path; a fusion without one
    takes its fused computation's root's, or failing that the last fused
    instruction's that has one. An instruction the compiler
    added with no metadata (a layout copy, an async copy, the window of a
    cumulative sum) takes the path of the nearest instruction whose value
    it reads, or failing that of the nearest that reads its value."""
    comps = _computations(hlo_text)
    fused_path = {}
    for c, ins in comps.items():
        paths = [(_op_name(line), root) for _, root, line in ins]
        root = next((p for p, r in paths if r and p), "")
        fused_path[c] = root or next(
            (p for p, _ in reversed(paths) if p), "")
    own: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = defaultdict(list)
    for ins in comps.values():
        for name, _, line in ins:
            fused = _FUSION.search(line)
            own[name] = _op_name(line) or (
                fused_path.get(fused.group(1), "") if fused else "")
            refs = [r for r in _REF.findall(line.split(" = ", 1)[1])
                    if r not in comps and r != name]
            operands[name] = refs
            for r in refs:
                users[r].append(name)
    return {n: p or _inherit(n, operands, own) or _inherit(n, users, own)
            for n, p in own.items()}


def mixed_fusions(hlo_text: str) -> FrozenSet[str]:
    """Fusions whose fused instructions lie under more than one of the
    top-level scopes."""
    comps = _computations(hlo_text)
    out = set()
    for ins in comps.values():
        for name, _, line in ins:
            fused = _FUSION.search(line)
            if not fused:
                continue
            tops = {top_scope(p) for p in (
                _op_name(l) for _, _, l in comps.get(fused.group(1), []))
                if p} - {UNSCOPED}
            if len(tops) > 1:
                out.add(name)
    return frozenset(out)


def split(events: Iterable[Tuple[float, float, str]], runs: int,
          scopes: Dict[str, str], mixed: FrozenSet[str] = frozenset()
          ) -> Dict[str, float]:
    """Seconds per program run of one chip's op events (start, end, short
    name; nested events are taken exclusive of their children), under
    each top-level scope, ``head/sample``, ``unscoped``, ``mixed`` and in
    ``total``."""
    out = dict.fromkeys(TOP + (SAMPLE, UNSCOPED, MIXED, "total"), 0.0)
    for op, secs in exclusive_times(list(events)).items():
        path = scopes.get(op, "")
        out[top_scope(path)] += secs
        if path == SAMPLE or path.startswith(SAMPLE + "/"):
            out[SAMPLE] += secs
        if op in mixed:
            out[MIXED] += secs
        out["total"] += secs
    return {k: v / max(runs, 1) for k, v in out.items()}


def module_name_of(hlo_text: str) -> str:
    """'HloModule jit_train_step, ...' -> 'jit_train_step'."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def module_ops(path: str, module: str
               ) -> List[Tuple[List[Tuple[float, float, str]], int, float]]:
    """For each chip of the trace at ``path`` on which ``module`` ran: the
    ``XLA Ops`` events (start s, end s, short name) that start inside one
    of its ``XLA Modules`` events, its number of runs, and its device
    seconds."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines.get("XLA Modules", [])
                      if module_name(e.name) == module)
        if not runs:
            continue
        events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                   short_op(e.name)) for e in lines.get("XLA Ops", [])
                  if any(s <= e.start_ns < t for s, t in runs)]
        out.append((events, len(runs),
                    sum(t - s for s, t in runs) * 1e-9))
    return out


def read(path: str, module: str, scopes: Dict[str, str],
         mixed: FrozenSet[str] = frozenset()) -> Dict[str, float]:
    """``split`` of ``module``'s ops in the trace at ``path``, averaged over
    the chips, with ``module_s``, the module's device seconds per run.
    Empty if the module never ran."""
    chips = module_ops(path, module)
    mean: Dict[str, float] = defaultdict(float)
    for events, runs, module_s in chips:
        got = split(events, runs, scopes, mixed)
        got["module_s"] = module_s / runs
        for k, v in got.items():
            mean[k] += v / len(chips)
    return dict(mean)


def host_spans(path: str, name: str) -> List[float]:
    """Seconds of each host event named ``name`` (a span the program's
    registry annotates) in the trace at ``path``. A span still open when
    the trace stopped is not in it."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return [e.duration_ns * 1e-9 for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name == name]
