"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The window is the benchmark's ``bench.window`` host span.  On each device
plane (``/device:TPU:<id>``) the ``XLA Ops`` line holds one event per
executed HLO instruction, named by its HLO text (``%fusion.12 = ...``);
control-flow instructions (``while``, ``conditional``, ``call``) enclose
the ops of their bodies.  From these:

  * busy: the union of the op intervals inside the window; idle is the
    rest of the window;
  * kernel time: the summed durations of the ops whose HLO text names a
    kernel (a Pallas kernel appears as a ``custom-call`` carrying its
    name);
  * collective time: ops that are all-reduce, all-gather, reduce-scatter,
    all-to-all or collective-permute (on either op line), and the part of
    it that overlaps no other op on that device;
  * the breakdown: the ten ops that took most time on the first device,
    and its idle time split by the innermost ``bench.*``/``engine.*`` host
    span under each gap's midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import numpy as np

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
CONTROL = ("while", "conditional", "call")
HOST_PREFIXES = ("bench.", "engine.")
WINDOW_SPAN = "bench.window"

_NAME = re.compile(r"%?([^\s=]+)")


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``all-reduce-start.3`` ->
    ``all-reduce-start``."""
    return re.sub(r"\.\d+$", "", name)


def is_collective(name: str) -> bool:
    return any(c in op_kind(name) for c in COLLECTIVES)


def union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (n, 2) intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def subtract(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the union of ``a`` that ``b``'s union does not cover."""
    a, b = union(a), union(b)
    total = 0.0
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def load(path: str):
    """Device op events and host spans of the one trace under ``path``.
    Returns ``({device_id: {"ops": [(name, text, start, end)],
    "async": [...]}}, [(name, start, end)])``, times in ns."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{path}: {len(files)} traces")
    pd = ProfileData.from_file(files[0])
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), {"ops": [], "async": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(
                    line.name)
                if key:
                    d[key].extend(
                        (op_name(e.name), e.name, e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return devices, host


def _label(host, t):
    """The innermost host span of the benchmark or engine around time t."""
    best = None
    for name, s, e in host:
        if s <= t < e and name != WINDOW_SPAN and (
                best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


def reduce(path: str, device_ids, kernels=()) -> dict:
    """Busy and idle time, kernel and collective time, and the breakdown,
    over the ``bench.window`` span; seconds, averaged over ``device_ids``
    where a quantity is per device."""
    devices, host = load(path)
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = spans[0]
    per = []
    for i in device_ids:
        d = devices.get(i, {"ops": [], "async": []})
        ops = [o for o in d["ops"] if o[3] > lo and o[2] < hi]
        iv = np.asarray([(o[2], o[3]) for o in ops], float).reshape(-1, 2)
        busy = union(clip(iv, lo, hi))
        coll = [o for o in ops + [a for a in d["async"]
                                  if a[3] > lo and a[2] < hi]
                if is_collective(o[0])]
        other = np.asarray([(o[2], o[3]) for o in ops
                            if not is_collective(o[0])
                            and op_kind(o[0]) not in CONTROL],
                           float).reshape(-1, 2)
        c_iv = clip(np.asarray([(o[2], o[3]) for o in coll],
                               float).reshape(-1, 2), lo, hi)
        per.append({
            "busy": length(busy), "busy_iv": busy, "ops": ops,
            "collective": length(union(c_iv)),
            "collective_exposed": subtract(c_iv, clip(other, lo, hi)),
            "kernels": {k: sum(min(o[3], hi) - max(o[2], lo) for o in ops
                               if k in o[1]) for k in kernels},
            "kernel_calls": {k: sum(1 for o in ops if k in o[1])
                             for k in kernels},
        })
    win = hi - lo
    first = per[0]
    top = collections.Counter()
    for name, text, s, e in first["ops"]:
        if op_kind(name) not in CONTROL:
            shape = text.split("= ", 1)[1].split(" ", 1)[0] \
                if "= " in text else ""
            top[f"{name} {shape}"[:96]] += (min(e, hi) - max(s, lo)) / 1e9
    gaps = collections.Counter()
    counts = collections.Counter()
    edges = np.concatenate([[lo], first["busy_iv"].reshape(-1), [hi]])
    for s, e in edges.reshape(-1, 2):
        if e > s:
            lab = _label(host, (s + e) / 2)
            gaps[lab] += (e - s) / 1e9
            counts[lab] += 1
    mean = lambda k: float(np.mean([p[k] for p in per])) / 1e9
    inside = collections.Counter(n for n, s, e in host if s >= lo and e <= hi)
    return {
        "path": path,
        "window_s": win / 1e9,
        "busy_s": mean("busy"),
        "idle_share": 1.0 - first["busy"] / win,
        "collective_s": mean("collective"),
        "collective_exposed_s": mean("collective_exposed"),
        "kernel_s": {k: float(np.mean([p["kernels"][k] for p in per])) / 1e9
                     for k in kernels},
        "kernel_calls": {k: per[0]["kernel_calls"][k] for k in kernels},
        "host_spans": dict(inside),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top.most_common(10)],
            "idle_gaps": [[f"{k} (gaps={counts[k]})", v]
                          for k, v in gaps.most_common(10)]},
    }
