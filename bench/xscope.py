"""Device time per layer of the program, from a profiler trace
(``.xplane.pb``).

The program opens a fixed table of layer scopes, each a
``jax.named_scope``; ``LAYER_SCOPES`` here is the benchmark's copy of it
(``repro/obs/trace.py``; a test keeps the two equal, so a change to the
program's table shows up as a change to the benchmark).  Every HLO op
keeps its name stack, and the trace holds it as the ``tf_op`` stat of the
op's event metadata; ``jax.profiler.ProfileData`` does not expose
metadata stats, so this module reads the protobuf wire format itself.

On each of the cell's device planes (the first ``chips`` TPU planes) every
``XLA Ops`` event is labelled with the innermost scope in its stack, or
``unscoped``; control-flow ops (``while`` ...) enclose their bodies, so the
busy time of the ``bench.window`` span is split among the innermost event
running at each instant, and the labels' times sum to the busy union that
``xtrace`` reports.  Besides:

  * the backward share of each scope (stacks with ``transpose(``);
  * ``assemble`` exposed: time in which an op under ``assemble`` ran (on
    either op line) and no op outside it did;
  * host idle: device idle time inside the engine's ``engine.admit``,
    ``engine.plan``, ``engine.upload`` and ``engine.consume`` spans;
  * the window's ``bench.step`` and ``engine.tick`` spans, to divide by.

Quantities per device are averaged over the cell's chips.  A program
without the table (or a trace without stacks) reads ``unscoped`` alone.
Results are memoised by path.
"""
from __future__ import annotations

import glob
import heapq
import os
import re

import numpy as np

from xtrace import CONTROL, HOST_PREFIXES, WINDOW_SPAN, clip, length, \
    op_kind, op_name, subtract, union

#: the program's layer scopes (``repro/obs/trace.py::LAYER_SCOPES``)
LAYER_SCOPES = ("embed", "attn", "kv_write", "fal_signal", "mlp",
                "assemble", "head", "sample", "optimizer")
UNSCOPED = "unscoped"
HOST_IDLE_SPANS = ("engine.admit", "engine.plan", "engine.upload",
                   "engine.consume")
OP_LINES = {"XLA Ops": "ops", "Async XLA Ops": "async"}
_DEVICE = re.compile(r"/device:TPU:(\d+)")
_STACK_PARTS = re.compile(r"[/():]")


# ------------------------------------------------------------ wire format
def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of one message: ints for varints,
    memoryview slices for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt in (1, 5):
            w = 8 if wt == 1 else 4
            v, i = b[i:i + w], i + w
        else:
            raise ValueError(f"wire type {wt}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(v):
    d = dict(_fields(v))
    return d.get(1, 0), d.get(2, b"")


def _plane(buf):
    """An XPlane: name, lines, and per event-metadata id its name and
    ``tf_op`` stack."""
    name, lines, meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta[_map_entry(v)[0]] = _map_entry(v)[1]
        elif f == 5:
            k, sm = _map_entry(v)
            stat_names[k] = _str(dict(_fields(sm)).get(2, b""))
    events = {}
    for k, v in meta.items():
        ev_name, stack = "", None
        for f, w in _fields(v):
            if f == 2:
                ev_name = _str(w)
            elif f == 5:
                st = dict(_fields(w))
                if stat_names.get(st.get(1)) == "tf_op":
                    stack = (_str(st[5]) if 5 in st
                             else stat_names.get(st.get(7), ""))
        events[k] = (ev_name, stack)
    return name, lines, events


def _line(buf):
    """An XLine: name, timestamp (ns) and its events as (metadata id,
    start ns, end ns), times on the trace's own clock in whole ns as
    ``ProfileData`` gives them (so sums agree with ``xtrace``'s)."""
    name, ts, evs = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            ts = v
        elif f == 4:
            d = dict(_fields(v))
            s = d.get(2, 0) // 1000
            evs.append((d.get(1, 0), s, s + d.get(3, 0) // 1000))
    return name, ts, [(m, float(ts + s), float(ts + e)) for m, s, e in evs]


def load(path: str):
    """Device ops and host spans of the one trace under ``path``:
    ``({device_id: {"ops": [(name, stack, start, end)], "async": [...]}},
    [(name, start, end)])``, times in ns."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{path}: {len(files)} traces")
    with open(files[0], "rb") as f:
        data = memoryview(f.read())
    devices, host = {}, []
    for fno, pbuf in _fields(data):
        if fno != 1:
            continue
        name, lines, events = _plane(pbuf)
        m = _DEVICE.fullmatch(name)
        if not m and not name.startswith("/host:"):
            continue
        d = devices.setdefault(int(m.group(1)), {"ops": [], "async": []}) \
            if m else None
        for lbuf in lines:
            lname, _, evs = _line(lbuf)
            if d is not None and lname in OP_LINES:
                d[OP_LINES[lname]].extend(
                    (op_name(events[k][0]), events[k][1], s, e)
                    for k, s, e in evs)
            elif d is None:
                host.extend((events[k][0], s, e) for k, s, e in evs
                            if events[k][0].startswith(HOST_PREFIXES))
    return devices, host


# ------------------------------------------------------------- reduction
def innermost_scope(stack: str):
    """The innermost ``LAYER_SCOPES`` name in an op's name stack, or None
    outside every layer; transforms (``transpose(jvp(...))``) are
    skipped."""
    for part in reversed(_STACK_PARTS.split(stack)):
        if part in LAYER_SCOPES:
            return part
    return None


def label(stack) -> str:
    return innermost_scope(stack or "") or UNSCOPED


def exclusive(ops, lo, hi):
    """Split the busy union of ``ops`` inside [lo, hi) among the innermost
    op running at each instant (the latest started; the shorter on a tie):
    {op index: ns}."""
    iv = sorted((max(s, lo), min(e, hi), i)
                for i, (_, _, s, e) in enumerate(ops)
                if min(e, hi) > max(s, lo))
    cuts = sorted({t for s, e, _ in iv for t in (s, e)})
    out, heap, j = {}, [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(iv) and iv[j][0] <= a:
            s, e, i = iv[j]
            heapq.heappush(heap, (-s, e - s, e, i))
            j += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            i = heap[0][3]
            out[i] = out.get(i, 0.0) + (b - a)
    return out


def _intervals(rows):
    return np.asarray([(s, e) for *_, s, e in rows], float).reshape(-1, 2)


def _device(d, lo, hi, idle_spans):
    ops = [o for o in d["ops"] if o[3] > lo and o[2] < hi]
    scope, back, no_stack = {}, {}, 0.0
    for i, t in exclusive(ops, lo, hi).items():
        stack = ops[i][1]
        lab = label(stack)
        scope[lab] = scope.get(lab, 0.0) + t
        if "transpose(" in (stack or ""):
            back[lab] = back.get(lab, 0.0) + t
        if not stack:
            no_stack += t
    leaf = lambda rows: [o for o in rows if op_kind(o[0]) not in CONTROL]
    asyn = [a for a in d["async"] if a[3] > lo and a[2] < hi]
    asm = [o for o in leaf(ops + asyn) if label(o[1]) == "assemble"]
    other = [o for o in leaf(ops) if label(o[1]) != "assemble"]
    busy = union(clip(_intervals(ops), lo, hi))
    idle = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    idle = idle[idle[:, 1] > idle[:, 0]]
    return {
        "busy": length(busy), "no_stack": no_stack,
        "scope": scope, "backward": back,
        "assemble_exposed": subtract(clip(_intervals(asm), lo, hi),
                                     clip(_intervals(other), lo, hi)),
        # idle inside the spans: the idle gaps less what the spans leave
        "host_idle": length(idle) - subtract(idle,
                                             clip(idle_spans, lo, hi)),
    }


_MEMO = {}


def reduce(path: str, chips: int) -> dict:
    """Per-layer device time over the ``bench.window`` span, in seconds,
    averaged over the first ``chips`` TPU planes; memoised by path."""
    key = (os.path.abspath(path), chips)
    if key in _MEMO:
        return _MEMO[key]
    devices, host = load(path)
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = win[0]
    spans = np.asarray([(s, e) for n, s, e in host
                        if n in HOST_IDLE_SPANS], float).reshape(-1, 2)
    per = [_device(devices[i], lo, hi, spans)
           for i in sorted(devices)[:chips]]
    mean = lambda f: float(np.mean([f(p) for p in per])) / 1e9 \
        if per else 0.0
    names = sorted({k for p in per for k in p["scope"]})
    inside = [n for n, s, e in host if s >= lo and e <= hi]
    out = {
        "path": path,
        "window_s": (hi - lo) / 1e9,
        "busy_s": mean(lambda p: p["busy"]),
        "scope_s": {k: mean(lambda p: p["scope"].get(k, 0.0))
                    for k in names},
        "backward_s": {k: mean(lambda p: p["backward"].get(k, 0.0))
                       for k in names},
        "no_stack_s": mean(lambda p: p["no_stack"]),
        "assemble_exposed_s": mean(lambda p: p["assemble_exposed"]),
        "host_idle_s": mean(lambda p: p["host_idle"]),
        "host_idle_spans": int(len(spans)),
        "steps": inside.count("bench.step"),
        "ticks": inside.count("engine.tick"),
    }
    _MEMO[key] = out
    return out


def per_unit(ctx):
    """(the reduction of a traced run, its number of ``bench.step`` spans
    on a training cell or ``engine.tick`` spans on a serving cell), or
    None where the run has no trace or the trace none of those spans."""
    path = ctx["run"].trace_path
    if not path:
        return None
    r = reduce(path, ctx["chips"])
    n = r["ticks"] if ctx["cell"].traffic["job"] == "serve" else r["steps"]
    return (r, n) if n else None


def scope_ms(ctx, scope: str):
    """Milliseconds of device time under ``scope`` per step or tick; None
    where no op carries the scope."""
    got = per_unit(ctx)
    if got is None or scope not in got[0]["scope_s"]:
        return None
    return 1e3 * got[0]["scope_s"][scope] / got[1]
