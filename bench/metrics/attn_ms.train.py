"""attn_ms.train: milliseconds of device time per train step under the
program's ``attn`` layer scope (ln1 and self-attention, forward and
backward, the blockwise scan's score blocks included), averaged over the
chips (``bench/xscope.py``).  Moves ``train_tokens_per_s``."""
import xscope


def read(ctx):
    return xscope.scope_ms(ctx, "attn")
