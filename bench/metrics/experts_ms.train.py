"""experts_ms.train: milliseconds of device time per train step under the
program's ``experts`` sub-scope of ``mlp`` (the held experts' sort,
gathers, grouped matmuls and combine, forward and backward; the chip's
grouped-matmul kernels carry no name stack and are counted by name,
``xsub.UNSCOPED_OPS``), averaged over the chips (``bench/xsub.py``).
Moves ``train_tokens_per_s``."""
import xsub


def read(ctx):
    return xsub.sub_ms(ctx, "experts")
