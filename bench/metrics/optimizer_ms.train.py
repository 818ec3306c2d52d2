"""optimizer_ms.train: milliseconds of device time per train step under
the program's ``optimizer`` layer scope (AdamW with its global norm and
clip), averaged over the chips (``bench/xscope.py``).  Moves
``train_tokens_per_s``."""
import xscope


def read(ctx):
    return xscope.scope_ms(ctx, "optimizer")
