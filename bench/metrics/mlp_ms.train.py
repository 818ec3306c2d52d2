"""mlp_ms.train: milliseconds of device time per train step under the
program's ``mlp`` layer scope (the FFN, forward and backward), averaged
over the chips (``bench/xscope.py``).  Moves ``train_tokens_per_s``."""
import xscope


def read(ctx):
    return xscope.scope_ms(ctx, "mlp")
