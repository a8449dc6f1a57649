"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

- busy time: the union of the intervals in which an operation ran on a
  device, inside the traced window, averaged over the devices; the idle
  share is 1 minus busy over the window;
- the device operations that took most time, each by its own time (less
  the operations nested in it);
- collective exposure: the time a collective-permute (the gossip
  exchange) ran on a device with no computation running there, found by
  the operation's opcode (``collective-permute-start``, ``-done``); other
  collectives, such as a loss's ``all-reduce``, are neither counted nor
  taken for computation;
- idle gaps attributed to what the host was doing: the innermost host event
  that covers the middle of each gap, under the harness's own ``bench.*``
  span that covers it.

The window runs from the start of the first unit span (``bench.round``,
``bench.solve``) to the end of the last. Reads nothing but the trace file.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field

PERMUTE = re.compile(r"collective-permute|ppermute", re.IGNORECASE)
COLLECTIVE = re.compile(
    r"collective|all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"ppermute|send|recv", re.IGNORECASE)
TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
#: what an operation's name looks like (an HLO instruction: ``fusion.12``,
#: ``collective-permute-done``); host bookkeeping that shares a line with
#: operations on the CPU (``ThunkExecutor::Execute``, ``end: dot.1``,
#: ``Rendezvous``) does not
OP_NAME = re.compile(r"^(?!(Invoke)?Rendezvous$)[A-Za-z_][\w.\-]*$")
HOST_SKIP = re.compile(r"^ThreadpoolListener::")


@dataclass
class Trace:
    """Device operations per device: ``(name, opcode, start_ns, end_ns)``;
    host events on every host thread: ``(name, start_ns, end_ns)``."""

    devices: dict[str, list[tuple[str, str, float, float]]] = field(
        default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


@functools.lru_cache(maxsize=1 << 16)
def hlo_op(text: str) -> tuple[str, str, str]:
    """``(name, opcode, result type)`` of an operation event. On a TPU the
    event is the HLO instruction's text, ``%fusion.7 = (bf16[..], ..)
    fusion(..), kind=..``; elsewhere it is the bare name, which is then also
    the opcode's source, and the type is unknown."""
    if not text.startswith("%") or " = " not in text:
        return text, re.sub(r"[.\d]+$", "", text), ""
    name, rest = text[1:].split(" = ", 1)
    end = rest.find(" ")
    if rest.startswith("("):                   # a tuple-shaped result
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end += 1
                break
    m = re.match(r" ([\w\-]+)\(", rest[end:])
    op = m.group(1) if m else name
    kind = re.search(r"kind=(k\w+)", rest)
    rtype = re.sub(r"\{[^}]*\}", "", rest[:end])
    return name, op + (" " + kind.group(1) if kind else ""), rtype[:60]


def load(path: str, device_plane=TPU_PLANE, ops_line=OPS_LINE) -> Trace:
    """Read one trace. ``device_plane`` (a regex on plane names) and
    ``ops_line`` (a prefix of line names) say where device operations are;
    the defaults are a TPU's. Where the host plane itself matches (on the
    CPU, XLA's operations run on host threads), the operations of all its
    matching lines are read as one device."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        is_dev = bool(re.match(device_plane, plane.name))
        for line in plane.lines:
            on_ops = is_dev and line.name.startswith(ops_line)
            if not on_ops and plane.name != HOST_PLANE:
                continue
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if on_ops:
                    if plane.name == HOST_PLANE and not OP_NAME.match(ev.name):
                        continue
                    name, op, rtype = hlo_op(ev.name)
                    label = f"{name} ({op}{' ' + rtype if rtype else ''})"
                    tr.devices.setdefault(plane.name, []).append(
                        (label, op, s, e))
                elif not HOST_SKIP.match(ev.name):
                    tr.host.append((ev.name, s, e))
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv, w0: float, w1: float) -> list[tuple[float, float]]:
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def length(iv) -> float:
    return sum(e - s for s, e in iv)


def subtract(a, b) -> list[tuple[float, float]]:
    """``a`` minus ``b``, both unions (sorted, disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, w0: float, w1: float) -> list[tuple[float, float]]:
    return subtract([(w0, w1)], busy)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

#: idle gaps shorter than this are summed under one label instead of being
#: attributed one by one (the short gaps between back-to-back operations)
SHORT_GAP_NS = 10_000.0
SHORT_GAP = "between operations (< 10 us)"


def _labels(points: list[float], host: list[tuple[str, float, float]]
            ) -> list[str]:
    """What the host was doing at each of ``points`` (sorted): the innermost
    ``bench.*`` span and the innermost other host event that cover it."""
    evs = sorted(host, key=lambda h: h[1])
    out, active, j = [], [], 0
    for t in points:
        while j < len(evs) and evs[j][1] <= t:
            active.append(evs[j])
            j += 1
        active = [h for h in active if h[2] > t]
        bench = [h for h in active if h[0].startswith("bench.")]
        other = [h for h in active if not h[0].startswith("bench.")]
        parts = [min(g, key=lambda h: h[2] - h[1])[0][:80]
                 for g in (bench, other) if g]
        out.append(" / ".join(parts) or "no host event")
    return out


def self_times(evs) -> list[float]:
    """Each operation's own time: its duration less that of the operations
    nested in it (a ``while`` holds its body's operations on the same
    line), so that time is counted once."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][2], -evs[i][3]))
    own = [e[3] - e[2] for e in evs]
    stack: list[int] = []
    for i in order:
        s, e = evs[i][2], evs[i][3]
        while stack and evs[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][3]) - s
        stack.append(i)
    return own


def _top(d: dict[str, float], k: int) -> list[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def reduce(tr: Trace, unit: str, top: int = 10) -> dict:
    """Window, busy, idle share, top operations, collective exposure and
    idle gaps by host activity, from the unit spans named ``unit``. Seconds
    are per device, averaged over the devices."""
    spans = [(s, e) for name, s, e in tr.host if name == unit]
    if not spans:
        raise ValueError(f"no {unit!r} span in the trace")
    if not tr.devices:
        raise ValueError("no device operations in the trace")
    w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    window = w1 - w0
    nd = len(tr.devices)
    busy_ns = exposed_ns = 0.0
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    has_collective = False
    host = [h for h in tr.host if h[2] > w0 and h[1] < w1]
    for dev, evs in tr.devices.items():
        busy = union(clip([(s, e) for *_, s, e in evs], w0, w1))
        busy_ns += length(busy)
        inside = [ev for ev in evs if ev[3] > w0 and ev[2] < w1]
        for ev, own in zip(inside, self_times(inside)):
            ops[ev[0]] = ops.get(ev[0], 0.0) + own / nd
        coll = [(s, e) for name, cat, s, e in evs
                if PERMUTE.search(cat or name)]
        if coll:
            has_collective = True
            other = union(clip([(s, e) for name, cat, s, e in evs
                                if not COLLECTIVE.search(cat or name)], w0, w1))
            exposed_ns += length(subtract(union(clip(coll, w0, w1)), other))
        long_gaps = []
        for s, e in gaps(busy, w0, w1):
            if e - s < SHORT_GAP_NS:
                idle[SHORT_GAP] = idle.get(SHORT_GAP, 0.0) + (e - s) / nd
            else:
                long_gaps.append((s, e))
        mids = [0.5 * (s + e) for s, e in long_gaps]
        for (s, e), lab in zip(long_gaps, _labels(mids, host)):
            idle[lab] = idle.get(lab, 0.0) + (e - s) / nd
    busy_s = busy_ns / nd / 1e9
    return {
        "window_s": window / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_ns / nd / window,
        "collective_exposed_share": (exposed_ns / nd / window
                                     if has_collective else None),
        "devices": nd,
        "units": len(spans),
        "unit_s": [(e - s) / 1e9 for s, e in sorted(spans)],
        "device_ops": [[n, v / 1e9] for n, v in _top(ops, top)],
        "idle_gaps": [[n, v / 1e9] for n, v in _top(idle, top)],
    }
