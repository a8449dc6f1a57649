"""Reduction of a JAX profiler trace (``.xplane.pb``) to the program's own
spans and named scopes (``repro.obs``).

- device time by scope: each device operation's own time (less the
  operations nested in it, as ``trace.self_times`` counts it), attributed to
  the innermost ``repro.<name>`` in the operation's ``op_name``; per unit
  span and averaged over the devices. The backward pass
  (``transpose(jvp(...))``) and rematerialised work
  (``rematted_computation``) carry the scope of their forward code, so they
  count under it;
- coverage: the share of that device time under any scope;
- host spans: every ``repro.*`` host span by name, with its count, its
  seconds and each occurrence's arguments (``restart=``, ``step=``).

On a TPU an operation's ``op_name`` is the ``tf_op`` stat of its event
metadata, which ``jax.profiler.ProfileData`` does not expose; ``op_names``
reads it from the serialized ``XSpace`` itself. The window is the one
``trace.reduce`` uses: the first unit span's start to the last one's end.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from bench import trace as trace_mod

#: the innermost named scope of an op_name; a path component
#: (``.../repro.attention/dot_general``) or a transform's argument
#: (``transpose(jvp(repro.mlp))``)
SCOPE = re.compile(r"repro\.(\w+)")
SPAN = "repro."
OP_NAME_STAT = "tf_op"


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``repro.<name>`` scope of an ``op_name``, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# the serialized XSpace: event metadata of the device planes
# ---------------------------------------------------------------------------

def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """``(field number, value)`` of one protobuf message: an int for varint
    fields, a memoryview for length-delimited ones (fixed-width ones are
    skipped)."""
    i, end = 0, len(b)
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield num, b[i:i + n]
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")


def _entry(b) -> tuple[int, memoryview | None]:
    """A map entry: ``(key, value message)``."""
    key, val = 0, None
    for num, v in _fields(b):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_names(path: str, device_plane=trace_mod.TPU_PLANE,
             stat: str = OP_NAME_STAT) -> dict[str, dict[str, str]]:
    """``{plane name: {event name: op_name}}`` for the planes matching
    ``device_plane``: the ``stat`` of each event metadata (a string, or a
    reference to an interned one). XSpace: planes = 1; XPlane: name = 2,
    event_metadata = 4, stat_metadata = 5; XEventMetadata: name = 2,
    stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1, str_value =
    5, ref_value = 7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for pn, v in _fields(plane):
            if pn == 2:
                name = bytes(v).decode()
            elif pn == 4:
                events.append(v)
            elif pn == 5:
                stats.append(v)
        if not re.match(device_plane, name):
            continue
        stat_names = {}
        for entry in stats:
            k, meta = _entry(entry)
            stat_names[k] = next((bytes(x).decode() for n, x in _fields(meta)
                                  if n == 2), "") if meta is not None else ""
        ids = {k for k, v in stat_names.items() if v == stat}
        table = out.setdefault(name, {})
        for entry in events:
            _, meta = _entry(entry)
            if meta is None:
                continue
            ev_name, op = "", None
            for n, x in _fields(meta):
                if n == 2:
                    ev_name = bytes(x).decode()
                elif n == 5:
                    sid = val = None
                    for sn, sv in _fields(x):
                        if sn == 1:
                            sid = sv
                        elif sn == 5:
                            val = bytes(sv).decode()
                        elif sn == 7:
                            val = stat_names.get(sv)
                    if sid in ids:
                        op = val
            if op:
                table[ev_name] = op
    return out


# ---------------------------------------------------------------------------
# loading and reducing
# ---------------------------------------------------------------------------

@dataclass
class Spans:
    """Device operations per device: ``(scope or None, start_ns, end_ns)``;
    host events on every host thread: ``(name, args, start_ns, end_ns)``,
    with the arguments of ``repro.*`` spans only."""

    devices: dict[str, list[tuple[str | None, float, float]]] = field(
        default_factory=dict)
    host: list[tuple[str, dict, float, float]] = field(default_factory=list)


def load(path: str, device_plane=trace_mod.TPU_PLANE,
         ops_line=trace_mod.OPS_LINE) -> Spans:
    """Read one trace as ``trace.load`` does, keeping each operation's scope
    and the ``repro.*`` spans' arguments."""
    from jax.profiler import ProfileData

    names = op_names(path, device_plane)
    sp = Spans()
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(re.match(device_plane, plane.name))
        table = names.get(plane.name, {})
        for line in plane.lines:
            on_ops = is_dev and line.name.startswith(ops_line)
            if not on_ops and plane.name != trace_mod.HOST_PLANE:
                continue
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if on_ops:
                    if (plane.name == trace_mod.HOST_PLANE
                            and not trace_mod.OP_NAME.match(ev.name)):
                        continue
                    sp.devices.setdefault(plane.name, []).append(
                        (scope_of(table.get(ev.name)), s, e))
                elif not trace_mod.HOST_SKIP.match(ev.name):
                    args = (dict(ev.stats) if ev.name.startswith(SPAN)
                            else {})
                    sp.host.append((ev.name, args, s, e))
    return sp


def reduce(sp: Spans, unit: str) -> dict:
    """Device seconds by scope per unit and device, coverage and the
    ``repro.*`` spans, inside the window of the unit spans named ``unit``."""
    units = [(s, e) for name, _, s, e in sp.host if name == unit]
    if not units:
        raise ValueError(f"no {unit!r} span in the trace")
    if not sp.devices:
        raise ValueError("no device operations in the trace")
    w0, w1 = min(s for s, _ in units), max(e for _, e in units)
    nd, nu = len(sp.devices), len(units)
    own: dict[str | None, float] = {}
    host = [h for h in sp.host if h[3] > w0 and h[2] < w1]
    for evs in sp.devices.values():
        inside = [(sc, None, s, e) for sc, s, e in evs if e > w0 and s < w1]
        for ev, t in zip(inside, trace_mod.self_times(inside)):
            own[ev[0]] = own.get(ev[0], 0.0) + t
    total = sum(own.values())
    spans: dict[str, dict] = {}
    for name, args, s, e in host:
        if name.startswith(SPAN):
            d = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                        "calls": []})
            d["count"] += 1
            d["seconds"] += (e - s) / 1e9
            d["calls"].append([args, (e - s) / 1e9])
    return {
        "units": nu,
        "devices": nd,
        "scope_s_per_unit": {sc: t / nd / nu / 1e9
                             for sc, t in sorted(own.items(),
                                                 key=lambda kv: -kv[1])
                             if sc is not None},
        "coverage": (1.0 - own.get(None, 0.0) / total) if total else 0.0,
        "spans": spans,
    }
