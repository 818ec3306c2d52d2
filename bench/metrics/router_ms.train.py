"""router_ms.train: milliseconds of device time per train step under the
program's ``router`` sub-scope of ``mlp`` (router matmul, scores, top-k,
gates and balance loss, forward and backward), averaged over the chips
(``bench/xsub.py``).  Moves ``train_tokens_per_s``."""
import xsub


def read(ctx):
    return xsub.sub_ms(ctx, "router")
