"""host_idle_ms.serve_tick: milliseconds per engine tick in which the chip
sat idle while the host was inside the engine's ``engine.admit``,
``engine.plan``, ``engine.upload`` or ``engine.consume`` span
(``bench/xscope.py``).  Read only where the trace has those spans.  Moves
``itl_p95_ms``."""
import xscope


def read(ctx):
    got = xscope.per_unit(ctx)
    if got is None or not got[0]["host_idle_spans"]:
        return None
    return 1e3 * got[0]["host_idle_s"] / got[1]
