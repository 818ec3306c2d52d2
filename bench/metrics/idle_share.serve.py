"""idle_share.serve: % of the traced window in which no operation ran on
the chip (``bench/xtrace.py``); the breakdown says what the host did in
those gaps.  Moves ``itl_p95_ms``."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
