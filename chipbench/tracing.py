"""Reduction of a ``jax.profiler`` trace to intervals, and the interval
arithmetic the metric readers share.

``reduce_xplane`` keeps, per TPU device, every event of its "XLA Ops"
line as ``[instruction, start_ns, duration_ns, opcode]``, and the host
spans the harness writes (``HOST_SPANS``) as ``[name, start_ns,
duration_ns]``.  Device and host events share the profiler's clock.
Container ops (``while`` and the like) span the ops of their bodies and
are left out of every sum.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("window", "data", "dispatch", "wait")
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# an op whose event spans the ops of its body
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def parse_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an "XLA Ops" event, whose name is
    the HLO instruction: ``%fusion.3 = bf16[8]{0} fusion(...), ...``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    m = _OPCODE.search(rest)
    return name.strip().lstrip("%"), m.group(1) if m else ""


def reduce_xplane(path: str) -> Dict:
    """``{"devices": {id: [[name, start_ns, dur_ns, opcode], ...]},
    "host": [[span, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([*parse_op(ev.name), ev.start_ns,
                                ev.duration_ns] for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events if ev.name in HOST_SPANS)
    for dev, ops in devices.items():
        devices[dev] = [[n, s, d, op] for n, op, s, d in ops]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


# -- interval arithmetic -------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union ``a`` not covered by the union ``b``."""
    out, b = [], union(b)
    for lo, hi in union(a):
        cur = lo
        for x, y in b:
            if y <= cur or x >= hi:
                continue
            if x > cur:
                out.append((cur, x))
            cur = max(cur, y)
        if cur < hi:
            out.append((cur, hi))
    return out


def window(trace: Dict) -> Interval:
    """The traced window: the harness's ``window`` host span."""
    spans = [e for e in trace["host"] if e[0] == "window"]
    if not spans:
        raise ValueError("the trace holds no 'window' host span")
    _, start, dur = spans[0]
    return start, start + dur


def op_intervals(ops: Sequence, kind: str = "all") -> List[Interval]:
    """Intervals of the ops that are not containers: ``all`` of them,
    the ``collective`` ones, or the ``compute`` ones (the rest)."""
    out = []
    for name, s, d, opcode in ops:
        if opcode in CONTAINERS:
            continue
        coll = bool(COLLECTIVE.match(opcode))
        if kind == "all" or (kind == "collective") == coll:
            out.append((s, s + d))
    return out


def busy(trace: Dict, dev: str) -> float:
    lo, hi = window(trace)
    return length(union(clip(op_intervals(trace["devices"][dev]), lo, hi)))


def busy_share(trace: Dict) -> float:
    """Union of device op intervals over the window, mean over devices."""
    lo, hi = window(trace)
    devs = sorted(trace["devices"])
    if not devs:
        return float("nan")
    return sum(busy(trace, d) for d in devs) / len(devs) / (hi - lo)


def kernel_events(trace: Dict, name: str) -> Tuple[int, float]:
    """(count, summed ns) of the events in the window of the Pallas
    kernel ``name`` (instructions named ``name`` or ``name.N``), over
    all devices."""
    pat = re.compile(rf"{re.escape(name)}(\.\d+)?$")
    lo, hi = window(trace)
    n, total = 0, 0.0
    for ops in trace["devices"].values():
        for op, s, d, _ in ops:
            if pat.match(op) and s >= lo and s + d <= hi:
                n += 1
                total += d
    return n, total


def exposed_collective_share(trace: Dict) -> float:
    """Share of the window in which a collective runs on a device and no
    compute op does, mean over devices."""
    lo, hi = window(trace)
    shares = []
    for ops in trace["devices"].values():
        coll = clip(op_intervals(ops, "collective"), lo, hi)
        comp = clip(op_intervals(ops, "compute"), lo, hi)
        shares.append(length(subtract(coll, comp)) / (hi - lo))
    return sum(shares) / len(shares) if shares else float("nan")


def idle_gaps(trace: Dict, dev: str) -> List[Tuple[str, float]]:
    """Idle intervals of device ``dev`` in the window, longest first, each
    labelled by the innermost host span open at the gap's midpoint
    ("none" where the host was in none)."""
    lo, hi = window(trace)
    gaps = subtract([(lo, hi)], clip(op_intervals(trace["devices"][dev]),
                                     lo, hi))
    spans = [e for e in trace["host"] if e[0] != "window"]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [e for e in spans if e[1] <= mid <= e[1] + e[2]]
        label = min(open_, key=lambda e: e[2])[0] if open_ else "none"
        out.append((label, (b - a) / 1e9))
    return sorted(out, key=lambda g: -g[1])


def top_ops(trace: Dict, n: int = 10) -> List[Tuple[str, float]]:
    """The device ops (containers left out) that took most time in the
    window, as "name opcode", summed over devices and divided by the
    device count (seconds per device)."""
    lo, hi = window(trace)
    tot: Dict[str, float] = {}
    for ops in trace["devices"].values():
        for name, s, d, opcode in ops:
            a, b = max(s, lo), min(s + d, hi)
            if b > a and opcode not in CONTAINERS:
                key = f"{name} {opcode}"
                tot[key] = tot.get(key, 0.0) + (b - a)
    k = max(len(trace["devices"]), 1)
    return [(name, t / k / 1e9)
            for name, t in sorted(tot.items(), key=lambda x: -x[1])[:n]]
