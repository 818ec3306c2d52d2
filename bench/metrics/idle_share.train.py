"""idle_share.train: % of the traced window in which no operation ran on
the first chip (``bench/xtrace.py``).  Moves ``train_tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
