"""sched.padding_share: mean % of the packed tick's flat token buffer that
carried padding over the window's ticks (``PagedEngine.stats()``
``padding_fraction``).  Moves ``itl_p95_ms``."""


def read(ctx):
    return 100.0 * ctx["run"].counters["padding_share"]
