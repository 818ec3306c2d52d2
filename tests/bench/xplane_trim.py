#!/usr/bin/env python3
"""Cut a profiler trace down to a short stretch of its benchmark window,
as the fixtures in ``data/*.xplane.pb`` were cut:

    python3 tests/bench/xplane_trim.py SRC.xplane.pb DST.xplane.pb LO HI [CHIPS]

``LO`` and ``HI`` are ns on the trace's own clock (``bench/xscope.py``'s
``load`` gives the window's).  The reader is ``bench/xscope.py``; this is
the protobuf writer it does not need.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "bench"))

from xscope import (_DEVICE, OP_LINES, _fields, _line, _map_entry,  # noqa: E402
                    _plane, _varint)
from xtrace import HOST_PREFIXES, WINDOW_SPAN  # noqa: E402


def _raw_fields(b):
    """(field number, value, the field's own bytes) of one message, the
    value as ``xscope._fields`` gives it."""
    i, n = 0, len(b)
    while i < n:
        at = i
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
        yield key >> 3, v, b[at:i]


def _put_varint(n):
    out = bytearray()
    while True:
        c = n & 0x7F
        n >>= 7
        out.append(c | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _put(f, v):
    """One field: a varint for an int, else length-delimited bytes."""
    if isinstance(v, int):
        return _put_varint(f << 3) + _put_varint(v)
    v = bytes(v)
    return _put_varint((f << 3) | 2) + _put_varint(len(v)) + v


def trim(src: str, dst: str, lo: float, hi: float, chips: int = 1):
    """Write the trace ``src`` (a ``.xplane.pb`` file) cut to [lo, hi)
    (ns, the trace's clock) to ``dst``: the first ``chips`` device planes'
    op lines and the host's benchmark and engine spans, with
    ``bench.window`` set to [lo, hi); events that overlap it are kept
    whole.  Every other plane, line and unused metadata goes."""
    with open(src, "rb") as f:
        data = memoryview(f.read())
    planes = [_plane(p) + (p,) for n, p in _fields(data) if n == 1]
    keep_dev = sorted(int(m.group(1)) for m in
                      (_DEVICE.fullmatch(p[0]) for p in planes) if m)[:chips]
    out = bytearray()
    for name, lines, events, pbuf in planes:
        m = _DEVICE.fullmatch(name)
        if (int(m.group(1)) not in keep_dev) if m \
                else not name.startswith("/host:"):
            continue
        used, new_lines = set(), []
        for lbuf in lines:
            lname, ts, _ = _line(lbuf)
            if m and lname not in OP_LINES:
                continue
            body, n_ev = bytearray(), 0
            for f, v, raw in _raw_fields(lbuf):
                if f != 4:
                    body += raw
                    continue
                d = dict(_fields(v))
                mid = d.get(1, 0)
                s = ts + d.get(2, 0) / 1e3
                e = s + d.get(3, 0) / 1e3
                ev_name = events.get(mid, ("", None))[0]
                if m is None and not ev_name.startswith(HOST_PREFIXES):
                    continue
                if ev_name == WINDOW_SPAN:
                    raw = _put(4, _put(1, mid)
                               + _put(2, int(round((lo - ts) * 1e3)))
                               + _put(3, int(round((hi - lo) * 1e3))))
                elif e <= lo or s >= hi:
                    continue
                used.add(mid)
                body += raw
                n_ev += 1
            if n_ev:
                new_lines.append(bytes(body))
        if not new_lines:
            continue
        pout = bytearray()
        for f, v, raw in _raw_fields(pbuf):
            if f == 3 or f == 4 and _map_entry(v)[0] not in used:
                continue
            pout += raw
        for lb in new_lines:
            pout += _put(3, lb)
        out += _put(1, pout)
    with open(dst, "wb") as f:
        f.write(bytes(out))


if __name__ == "__main__":
    a = sys.argv[1:]
    if len(a) not in (4, 5):
        sys.exit(__doc__)
    trim(a[0], a[1], float(a[2]), float(a[3]), int(a[4]) if len(a) > 4 else 1)
