"""Device time per sub-scope of a layer, from a profiler trace
(``.xplane.pb``).

The program opens scopes inside one layer's scope
(``repro/obs/trace.py::SUB_SCOPES``: ``router`` and ``experts`` inside
``mlp``); ``SUB_SCOPES`` here is the benchmark's copy (a test keeps the two
equal).  ``bench/xscope.py`` knows only the layer scopes and counts these
ops under their layer, so what it reads does not change; this module
splits the same busy union of the ``bench.window`` span, each instant to
the innermost op running (``xscope.exclusive``), and labels an op by the
innermost layer scope or sub-scope in its ``tf_op`` name stack, or, where
the stack holds none, by its name (``UNSCOPED_OPS``).  A program without
sub-scopes reads none.  Quantities per device are averaged
over the cell's chips; results are memoised by path.
"""
from __future__ import annotations

import os
import re

import numpy as np

import xscope
from xtrace import WINDOW_SPAN

#: the program's sub-scopes, each with its layer
#: (``repro/obs/trace.py::SUB_SCOPES``)
SUB_SCOPES = {"router": "mlp", "experts": "mlp"}
_STACK_PARTS = re.compile(r"[/():]")
#: ops whose name stack holds no scope, by the prefix of their name, with
#: the sub-scope they run under: on the TPU, ``jax.lax.ragged_dot`` lowers
#: to grouped-matmul kernels named ``ragged-dot-none.N`` (and their
#: ``ragged-dot-metadata``) whose stack is their own name, and the program
#: calls it in ``experts`` alone (``models/moe.py::moe_apply_held``)
UNSCOPED_OPS = {"ragged-dot": "experts"}


def innermost(stack: str):
    """The innermost layer scope or sub-scope in an op's name stack, or
    None outside every one."""
    for part in reversed(_STACK_PARTS.split(stack or "")):
        if part in SUB_SCOPES or part in xscope.LAYER_SCOPES:
            return part
    return None


def label(name: str, stack: str):
    """``innermost(stack)``, or the sub-scope ``UNSCOPED_OPS`` gives an op
    of that name outside every scope."""
    lab = innermost(stack)
    if lab is None:
        for prefix, sub in UNSCOPED_OPS.items():
            if name.startswith(prefix):
                return sub
    return lab


_MEMO = {}


def reduce(path: str, chips: int) -> dict:
    """``sub_s``: seconds of device time under each sub-scope in the
    window, averaged over the first ``chips`` TPU planes; ``steps``: the
    window's ``bench.step`` spans."""
    key = (os.path.abspath(path), chips)
    if key in _MEMO:
        return _MEMO[key]
    devices, host = xscope.load(path)
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = win[0]
    per = []
    for i in sorted(devices)[:chips]:
        ops = [o for o in devices[i]["ops"] if o[3] > lo and o[2] < hi]
        sub = {}
        for j, t in xscope.exclusive(ops, lo, hi).items():
            lab = label(ops[j][0], ops[j][1])
            if lab in SUB_SCOPES:
                sub[lab] = sub.get(lab, 0.0) + t
        per.append(sub)
    names = sorted({k for p in per for k in p})
    out = {"sub_s": {k: float(np.mean([p.get(k, 0.0) for p in per])) / 1e9
                     for k in names},
           "steps": sum(1 for n, s, e in host
                        if n == "bench.step" and s >= lo and e <= hi)}
    _MEMO[key] = out
    return out


def sub_ms(ctx, name: str):
    """Milliseconds of device time under sub-scope ``name`` per train
    step; None where the run has no trace, the trace no step, or no op
    carries the sub-scope."""
    path = ctx["run"].trace_path
    if not path:
        return None
    r = reduce(path, ctx["chips"])
    if not r["steps"] or name not in r["sub_s"]:
        return None
    return 1e3 * r["sub_s"][name] / r["steps"]
