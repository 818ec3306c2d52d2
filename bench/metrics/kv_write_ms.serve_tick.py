"""kv_write_ms.serve_tick: milliseconds of device time per engine tick
under the program's ``kv_write`` layer scope (``bench/xscope.py``).  The
scope holds two things: the scatter of each tick's K/V rows (and scales)
into the page pools, and the paged layer scan itself, which slices each
layer's pools out of the stacked cache (the layer's weights with them),
changes their layout and writes the pools back.  Ops of the layer body
that no inner scope claims also land in the scan's scope; on the chip XLA
fuses the residual adds into other ops, so none does (the scoped fixture
pins the split).  Moves ``itl_p95_ms``."""
import xscope


def read(ctx):
    return xscope.scope_ms(ctx, "kv_write")
