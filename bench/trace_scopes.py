"""Split each jitted program's device time by the model's named scopes.

The program wraps its work in ``jax.named_scope`` under the names in
``SCOPES``.  XLA keeps the scope path in each operation's ``op_name``
metadata, and the profiler writes it as the ``tf_op`` stat of the
operation's event metadata on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, e.g.
``jit(serve_step)/layers/while/body/attn/dot_general``.
``jax.profiler.ProfileData`` gives no event metadata, so this module
reads the ``.xplane.pb`` (an ``XSpace`` protocol buffer) itself, with a
decoder of the few fields it needs.

Within the traced window (the host span ``window``), each operation's
clipped device time goes to the innermost name of ``SCOPES`` on its
path, in the program of the ``XLA Modules`` event that holds it; the
ops that hold others (``while``, ``conditional``, ``call``) are left
out, as :mod:`bench.trace_reduce` leaves them out.  ``unscoped`` is the
rest of each program's device time: ops with no scope on their path
(copies and other ops XLA makes with no ``op_name``) and the stretches
inside a program's run in which no op ran.  So a program's scopes and
``unscoped`` sum to its device time.  Seconds, averaged over chips.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from bench.trace_reduce import (CONTAINERS, MODULES_LINE, OPS_LINE, _clip,
                                find_xplane, op_name, program_name)

SCOPES = ("embed", "weight_cast", "layers", "attn", "mlp", "head")
UNSCOPED = "unscoped"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = "/device:TPU:"
TF_OP = "tf_op"


# ------------------------------------------------------ protocol buffer
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint, a ``(start, end)`` byte range
    for a length-delimited field.  Fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span: tuple[int, int]) -> tuple[int, int]:
    """The value of one map entry (key 1, value 2) of a plane."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[1], span[1])


def _plane(buf: bytes, span: tuple[int, int]):
    """The lines and event metadata of one ``XPlane`` (lines 3,
    event_metadata 4, stat_metadata 5).  Each line is ``(name,
    timestamp_ns, [event byte range...])``; the metadata maps an id to
    ``(name, tf_op or None)``."""
    lines, meta_spans, stat_names = [], [], {}
    for f, v in _fields(buf, *span):
        if f == 3:
            lines.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            sid, sname = 0, ""
            for sf, sv in _fields(buf, *_map_value(buf, v)):
                if sf == 1:
                    sid = sv
                elif sf == 2:
                    sname = _text(buf, sv)
            stat_names[sid] = sname
    tf_op_id = next((k for k, n in stat_names.items() if n == TF_OP), None)
    meta: dict[int, tuple[str, str | None]] = {}
    for span_ in meta_spans:
        mid, mname, path = 0, "", None
        for mf, mv in _fields(buf, *_map_value(buf, span_)):
            if mf == 1:
                mid = mv
            elif mf == 2:
                mname = _text(buf, mv)
            elif mf == 5 and tf_op_id is not None:
                stat = dict(_fields(buf, *mv))   # XStat: id 1, str 5, ref 7
                if stat.get(1) == tf_op_id:
                    if 5 in stat:
                        path = _text(buf, stat[5])
                    elif 7 in stat:
                        path = stat_names.get(stat[7])
        meta[mid] = (mname, path)
    kept = []
    for line in lines:
        lname, ts, events = "", 0, []
        for lf, lv in _fields(buf, *line):
            if lf == 2:
                lname = _text(buf, lv)
            elif lf == 3:
                ts = lv
            elif lf == 4:
                events.append(lv)
        kept.append((lname, ts, events))
    return kept, meta


def _events(buf: bytes, line) -> list[tuple[int, float, float]]:
    """``(metadata id, start s, end s)`` of each ``XEvent`` of a line
    (metadata_id 1, offset_ps 2, duration_ps 3), on the line's clock."""
    _, ts_ns, spans = line
    base_ps = ts_ns * 1000
    out = []
    for span in spans:
        mid = off = dur = 0
        for f, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = base_ps + off
        out.append((mid, start * 1e-12, (start + dur) * 1e-12))
    return out


def read_scoped_events(path: Path, window: str):
    """The host span ``window`` as ``(start, end)`` (None where the trace
    has none) and, per chip, its ``ops`` as ``(HLO name, op_name path,
    start, end)`` and its ``modules`` as ``(name, start, end)``, in
    seconds on the trace's clock."""
    buf = Path(path).read_bytes()
    win = None
    chips: dict[str, dict[str, list]] = {}
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:                                  # XSpace.planes
            continue
        # a plane's name comes before its lines: read it alone first
        pname = next((_text(buf, v) for pf, v in _fields(buf, *span)
                      if pf == 2), "")
        if pname == HOST_PLANE:
            lines, meta = _plane(buf, span)
            ids = {k for k, (n, _) in meta.items() if n == window}
            for line in lines:
                hit = [(s, e) for mid, s, e in _events(buf, line)
                       if mid in ids]
                if hit:
                    win = hit[0]
                    break
        elif pname.startswith(DEVICE_PLANE) and \
                pname[len(DEVICE_PLANE):].isdigit():
            lines, meta = _plane(buf, span)
            chip = {"ops": [], "modules": []}
            for line in lines:
                if line[0] not in (OPS_LINE, MODULES_LINE):
                    continue
                for mid, s, e in _events(buf, line):
                    mname, mpath = meta.get(mid, ("", None))
                    if line[0] == OPS_LINE:
                        chip["ops"].append((op_name(mname), mpath, s, e))
                    else:
                        chip["modules"].append((mname, s, e))
            if chip["ops"] or chip["modules"]:
                chips[pname] = chip
    return win, chips


# ------------------------------------------------------------ reduction
def innermost_scope(path: str | None) -> str | None:
    """The last name of ``SCOPES`` among the components of an
    ``op_name`` path before the operation's own name, or None."""
    if not path:
        return None
    for part in reversed(path.split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


def reduce_scopes(window: tuple[float, float], chips) -> dict:
    """``{program: {scope: seconds, "unscoped": seconds}}`` of the
    output of :func:`read_scoped_events` (see the module's doc)."""
    lo, hi = window
    out: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for chip in chips.values():
        runs = sorted((s, e, program_name(n)) for n, s, e in chip["modules"])
        starts = [s for s, _, _ in runs]
        for s, e, prog in runs:
            iv = _clip(s, e, lo, hi)
            if iv:
                out[prog][UNSCOPED] += iv[1] - iv[0]
        for name, path, s, e in chip["ops"]:
            scope = innermost_scope(path)
            iv = _clip(s, e, lo, hi)
            if scope is None or not iv or name.startswith(CONTAINERS):
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or runs[k][1] < e:
                continue                    # not inside a program's run
            prog = runs[k][2]
            out[prog][scope] += iv[1] - iv[0]
            out[prog][UNSCOPED] -= iv[1] - iv[0]
    n = max(len(chips), 1)
    return {p: {k: v / n for k, v in sc.items()} for p, sc in out.items()}


def scope_seconds(directory: Path, window: str):
    """Reduce the trace under ``directory``: ``(window seconds, split)``
    with the split of :func:`reduce_scopes`, or None where the
    directory holds no trace or the trace no span ``window``."""
    try:
        path = find_xplane(directory)
    except FileNotFoundError:
        return None
    win, chips = read_scoped_events(path, window)
    if win is None:
        return None
    return win[1] - win[0], reduce_scopes(win, chips)
