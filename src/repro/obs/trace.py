"""Low-overhead span/event tracer exporting Chrome trace-event JSON.

The exported file loads directly in Perfetto (ui.perfetto.dev) or
chrome://tracing: a ``{"traceEvents": [...]}`` object whose events follow
the Trace Event Format — ``X`` complete events for spans, ``i`` instants
for lifecycle transitions, ``b``/``e`` async pairs for per-request
lifecycle spans and ``C`` counter samples.

Design constraints (the serving hot loop calls this every tick):

* off-by-default — a disabled tracer's ``span()`` returns a shared
  no-op context manager and records nothing;
* monotonic clocks — timestamps come from ``time.perf_counter_ns``
  relative to the tracer's epoch, never wall clocks;
* no I/O until ``write()``/``export()`` — events accumulate in a list.

``span(..., annotate=True)`` additionally enters
``jax.profiler.TraceAnnotation`` so spans emitted around jitted dispatches
line up with XLA device traces when ``jax.profiler`` captures are taken;
the export's ``otherData`` carries the epoch on both ``perf_counter`` and
the wall clock the profiler stamps its events with, so the two traces can
be laid over each other.

``LAYER_SCOPES`` is the fixed table of layer names the programs open with
``layer_scope`` (a ``jax.named_scope``): they exist only in the traced
program's metadata and cost nothing at run time.  ``SUB_SCOPES`` name parts
inside one layer (``sub_scope``) in the same way.
"""
from __future__ import annotations

import json
import re
import time
from typing import Any, Dict, List


class _NullContext:
    """Reusable no-op context manager (cheaper than nullcontext per call)."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullContext()

#: layer scopes of the train step and the packed serving tick; each op of a
#: compiled program carries its name stack, and a trace reader takes the
#: innermost of these names in it (the ``obs`` package docstring maps each
#: to the code it covers)
LAYER_SCOPES = ("embed", "attn", "kv_write", "fal_signal", "mlp",
                "assemble", "head", "sample", "optimizer")


_STACK_PARTS = re.compile(r"[/():]")


def innermost_scope(stack: str):
    """The innermost ``LAYER_SCOPES`` name in an op's name stack (its HLO
    ``op_name``, a device trace's ``tf_op``), or None outside every layer.
    Transforms wrap the stack (``transpose(jvp(...))``) and are skipped."""
    for part in reversed(_STACK_PARTS.split(stack)):
        if part in LAYER_SCOPES:
            return part
    return None


def layer_scope(name: str):
    """``jax.named_scope`` for one name of ``LAYER_SCOPES``."""
    if name not in LAYER_SCOPES:
        raise ValueError(f"{name!r} is not a layer scope {LAYER_SCOPES}")
    import jax
    return jax.named_scope(name)


#: scopes opened inside one layer scope, each with the layer it lies in;
#: a reader of ``LAYER_SCOPES`` alone counts their ops under that layer
SUB_SCOPES = {"router": "mlp", "experts": "mlp"}


def sub_scope(name: str):
    """``jax.named_scope`` for one name of ``SUB_SCOPES``, opened inside
    its layer's scope."""
    if name not in SUB_SCOPES:
        raise ValueError(f"{name!r} is not a sub-scope {tuple(SUB_SCOPES)}")
    import jax
    return jax.named_scope(name)


class _Span:
    """Context manager recording one ``X`` complete event on exit."""
    __slots__ = ("tracer", "name", "cat", "tid", "args", "t0", "_ann")

    def __init__(self, tracer, name, cat, tid, args, annotate):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self._ann = None
        if annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t = self.tracer
        ev = {"name": self.name, "cat": self.cat, "ph": "X",
              "pid": t.pid, "tid": self.tid,
              "ts": (self.t0 - t.epoch_ns) / 1e3, "dur": dur / 1e3}
        if self.args:
            ev["args"] = self.args
        t._events.append(ev)
        return False


class Tracer:
    """Span/event collector; ``enabled=False`` (default) records nothing."""

    def __init__(self, enabled: bool = True, pid: int = 0,
                 process_name: str = "repro-engine"):
        self.enabled = enabled
        self.pid = pid
        self.process_name = process_name
        self._events: List[Dict[str, Any]] = []
        self._set_epoch()

    def _set_epoch(self):
        self.epoch_ns = time.perf_counter_ns()
        self.epoch_wall_ns = time.time_ns()

    # ------------------------------------------------------------- emit ----
    def _ts(self) -> float:
        return (time.perf_counter_ns() - self.epoch_ns) / 1e3

    def span(self, name: str, cat: str = "engine", tid: int = 0,
             annotate: bool = False, **args):
        """Context manager timing a span; ``annotate=True`` nests a
        ``jax.profiler.TraceAnnotation`` so XLA profiles align."""
        if not self.enabled:
            return _NULL_CTX
        return _Span(self, name, cat, tid, args or None, annotate)

    def instant(self, name: str, cat: str = "lifecycle", tid: int = 0,
                **args):
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "pid": self.pid, "tid": tid, "ts": self._ts()}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def begin_async(self, name: str, aid: int, cat: str = "request", **args):
        """Open an async span (rendered as a track-spanning bar keyed by
        ``aid`` — one per request in the engine's lifecycle trace)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "b", "id": aid,
              "pid": self.pid, "tid": 0, "ts": self._ts()}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def end_async(self, name: str, aid: int, cat: str = "request", **args):
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "e", "id": aid,
              "pid": self.pid, "tid": 0, "ts": self._ts()}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, value: float, cat: str = "metrics"):
        if not self.enabled:
            return
        self._events.append(
            {"name": name, "cat": cat, "ph": "C", "pid": self.pid, "tid": 0,
             "ts": self._ts(), "args": {name: value}})

    # ----------------------------------------------------------- export ----
    @property
    def events(self) -> List[Dict[str, Any]]:
        return self._events

    def clear(self):
        self._events.clear()
        self._set_epoch()

    def export(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable)."""
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
                 "ts": 0, "args": {"name": self.process_name}}]
        return {"traceEvents": meta + self._events,
                "displayTimeUnit": "ms",
                "otherData": {"epoch_perf_counter_ns": self.epoch_ns,
                              "epoch_wall_ns": self.epoch_wall_ns}}

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.export(), f)
        return path


#: shared disabled tracer — the engines' default, so the untraced hot loop
#: pays one attribute load + one no-op context per span site
NULL_TRACER = Tracer(enabled=False)


def validate_chrome_trace(obj: dict) -> int:
    """Schema check for an exported trace (CI gate + tests): returns the
    event count, raising ``ValueError`` on any malformed event."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be an object with 'traceEvents'")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    for ev in events:
        if not isinstance(ev, dict):
            raise ValueError(f"event is not an object: {ev!r}")
        for key in ("name", "ph", "pid", "tid", "ts"):
            if key not in ev:
                raise ValueError(f"event missing '{key}': {ev!r}")
        if ev["ph"] not in ("X", "i", "b", "e", "C", "M"):
            raise ValueError(f"unknown phase {ev['ph']!r}: {ev!r}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"complete event missing 'dur': {ev!r}")
        if ev["ph"] in ("b", "e") and "id" not in ev:
            raise ValueError(f"async event missing 'id': {ev!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            raise ValueError(f"bad ts: {ev!r}")
    return len(events)
