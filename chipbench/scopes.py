"""Attribution of device time to the program's layers.

The program names its layers with ``jax.named_scope`` (the names are in
``repro.obs.scopes``); the compiler keeps each name in the ``op_name``
metadata of every instruction the layer lowers to, forward and
backward.  ``op_scopes`` parses the compiled step's text into
``{instruction: [op_name, scope, phase]}``, which joins the device
trace (whose "XLA Ops" events are named by instruction) to the layers.

``scope_of`` reads one ``op_name``, a ``/``-separated path such as

    jit(train_step)/transpose(jvp(layers))/while/body/closed_call/
        checkpoint/rematted_computation/attention/attention_core/...

* scope: the innermost segment that names a known scope, through
  transform wrappers (``jvp(layers)`` matches ``layers``), or None;
* phase: ``bwd`` under a ``transpose(`` transform, ``remat`` where a
  ``rematted_computation`` segment follows the last such transform (the
  forward recomputed for the backward pass), else ``fwd``.  A
  ``custom_vjp`` backward rule's own recompute of its reference forward
  (``.../attention_core/jvp()/...``) lies under the outer transpose and
  is ``bwd``.

``program_spans`` keeps the program's host spans (names starting with
``data.``) of a ``.xplane.pb``; they share the device trace's clock.
Only ``program_spans`` imports jax (to read the profile).  A program
that names no layers (no ``repro.obs.scopes``) leaves every
instruction unscoped.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import tracing

try:
    from repro.obs.scopes import ALL as KNOWN
except ImportError:  # a program that names no layers
    KNOWN = ()

PROGRAM_SPAN_PREFIX = "data."
PHASES = ("fwd", "remat", "bwd")
REMAT = "rematted_computation"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s+=\s")
_WRAPPER = re.compile(r"^[A-Za-z_][\w\-]*\((.*)\)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_METADATA = re.compile(r',\s*metadata=\{(?:[^{}"]|"[^"]*")*\}')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _unwrap(segment: str) -> Tuple[str, List[str]]:
    """``transpose(jvp(layers))`` -> (``layers``, [``transpose``,
    ``jvp``])."""
    transforms = []
    m = _WRAPPER.match(segment)
    while m:
        transforms.append(segment[: segment.index("(")])
        segment = m.group(1)
        m = _WRAPPER.match(segment)
    return segment, transforms


def scope_of(op_name: str) -> Tuple[Optional[str], str]:
    """(innermost known scope or None, phase) of one ``op_name``."""
    scope, phase = None, "fwd"
    for segment in op_name.split("/"):
        inner, transforms = _unwrap(segment)
        if "transpose" in transforms:
            phase = "bwd"
        if inner == REMAT and phase != "fwd":
            phase = "remat"
        if inner in KNOWN:
            scope = inner
    return scope, phase


def _operands(rest: str, opcode: str) -> List[str]:
    """Operand names of an instruction, from the text after its ``=``."""
    i = rest.find(opcode + "(")
    if i < 0:
        return []
    depth, j = 0, i + len(opcode)
    for j in range(i + len(opcode), len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            break
    return _OPERAND.findall(rest[i + len(opcode):j])


def parse_hlo(text: str) -> Dict[str, Dict]:
    """``{instruction: {"computation", "opcode", "op_name", "calls",
    "operands", "root"}}`` of a compiled module's text."""
    out: Dict[str, Dict] = {}
    comp = None
    for line in text.splitlines():
        if comp is not None and _INSTRUCTION.match(line):
            stripped = line.strip()
            root = stripped.startswith("ROOT ")
            name, opcode = tracing.parse_op(stripped.removeprefix("ROOT "))
            rest = stripped.partition(" = ")[2]
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            out[name] = {"computation": comp, "opcode": opcode,
                         "op_name": op.group(1) if op else None,
                         "calls": calls.group(1) if calls else None,
                         "operands": _operands(rest, opcode), "root": root}
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
        elif line.startswith("}"):
            comp = None
    return out


def op_scopes(text: str) -> Dict[str, List]:
    """``{instruction: [op_name, scope, phase]}`` for every instruction
    of a compiled module.  An instruction without an ``op_name`` of its
    own, which the compiler made, takes the one of its called
    computation (the root's, else the first there), else of its first
    operand that has one."""
    instrs = parse_hlo(text)
    by_comp: Dict[str, List[Dict]] = {}
    for ins in instrs.values():
        by_comp.setdefault(ins["computation"], []).append(ins)
    resolved: Dict[str, Optional[str]] = {}

    def op_name(name: str) -> Optional[str]:
        if name in resolved:
            return resolved[name]
        resolved[name] = None           # guards a cycle
        ins = instrs[name]
        op = ins["op_name"]
        if op is None and ins["calls"] in by_comp:
            body = sorted(by_comp[ins["calls"]], key=lambda i: not i["root"])
            op = next((i["op_name"] for i in body if i["op_name"]), None)
        for x in ins["operands"] if op is None else ():
            op = op_name(x) if x in instrs else None
            if op:
                break
        resolved[name] = op
        return op

    out = {}
    for name in instrs:
        op = op_name(name)
        scope, phase = scope_of(op) if op else (None, "fwd")
        out[name] = [op, scope, phase]
    return out


def strip_tables(text: str) -> str:
    """The module's text without its stack-frame tables (source file
    names and lines)."""
    lines, skip = [], False
    for line in text.splitlines():
        if line.strip() in _TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
            continue
        if not skip:
            lines.append(line)
    return "\n".join(lines) + "\n"


def strip_metadata(text: str) -> str:
    """The module's text without ``metadata={...}`` and the stack-frame
    tables: what remains is the program itself."""
    return _METADATA.sub("", strip_tables(text))


# -- device time by scope -------------------------------------------------

def scope_intervals(trace: Dict, scopes: Dict[str, List], dev: str
                    ) -> Dict[Tuple[Optional[str], str], List]:
    """Device ``dev``'s op intervals in the window (containers left
    out), grouped by (scope, phase); instructions missing from
    ``scopes`` go under (``"?"``, ``"?"``), unscoped ones under (None,
    phase)."""
    lo, hi = tracing.window(trace)
    out: Dict = {}
    for name, s, d, opcode in trace["devices"][dev]:
        if opcode in tracing.CONTAINERS:
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        key = tuple(scopes[name][1:]) if name in scopes else ("?", "?")
        out.setdefault(key, []).append((a, b))
    return out


def scope_ms(trace: Dict, scopes: Dict[str, List], steps: int
             ) -> Dict[Tuple[Optional[str], str], float]:
    """ms per step per device of each (scope, phase): the union of its
    op intervals in the window, summed over devices, over the device
    count and ``steps``."""
    devs = sorted(trace["devices"])
    tot: Dict = {}
    for dev in devs:
        for key, iv in scope_intervals(trace, scopes, dev).items():
            tot[key] = tot.get(key, 0.0) + tracing.length(tracing.union(iv))
    return {k: v / 1e6 / len(devs) / steps for k, v in tot.items()}


def layer_ms(ms: Dict[Tuple[Optional[str], str], float], scope: str,
             phases: Iterable[str] = PHASES) -> Optional[float]:
    """The sum of ``scope``'s ``phases`` in a ``scope_ms`` result, None
    where the trace holds none of them."""
    got = [v for (s, p), v in ms.items() if s == scope and p in phases]
    return sum(got) if got else None


def coverage(trace: Dict, scopes: Dict[str, List]) -> Dict[str, float]:
    """Shares of the busy device time in the window (union per device,
    mean over devices) that falls on instructions of ``scopes``
    (``mapped``) and under a known scope (``scoped``)."""
    shares = {"mapped": [], "scoped": []}
    for dev in sorted(trace["devices"]):
        groups = scope_intervals(trace, scopes, dev)
        busy = tracing.length(tracing.union(
            iv for g in groups.values() for iv in g))
        if not busy:
            continue
        mapped = [iv for k, g in groups.items() if k[0] != "?" for iv in g]
        scoped = [iv for k, g in groups.items() if k[0] not in ("?", None)
                  for iv in g]
        shares["mapped"].append(tracing.length(tracing.union(mapped)) / busy)
        shares["scoped"].append(tracing.length(tracing.union(scoped)) / busy)
    return {k: sum(v) / len(v) if v else float("nan")
            for k, v in shares.items()}


# -- the program's host spans --------------------------------------------

def program_spans(path: str) -> List:
    """``[[name, start_ns, dur_ns], ...]`` of the host events of a
    ``.xplane.pb`` whose names start with ``data.``, any thread, sorted
    by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend([ev.name, ev.start_ns, ev.duration_ns]
                       for ev in line.events
                       if ev.name.startswith(PROGRAM_SPAN_PREFIX))
    return sorted(out, key=lambda e: e[1])
