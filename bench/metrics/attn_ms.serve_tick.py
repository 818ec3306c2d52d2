"""attn_ms.serve_tick: milliseconds of device time per engine tick
(``engine.tick`` spans of the window) under the program's ``attn`` layer
scope, the K/V page writes (``kv_write``) left out (``bench/xscope.py``).
Moves ``itl_p95_ms``."""
import xscope


def read(ctx):
    return xscope.scope_ms(ctx, "attn")
