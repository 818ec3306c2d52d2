"""collective_exposed_ms.train: per train step, the milliseconds in which a
collective ran on a chip and no other operation did, averaged over the
chips (``bench/xtrace.py``).  Read only where the step has collectives.
Moves ``train_tokens_per_s``."""


def read(ctx):
    tr = ctx["trace"]
    steps = tr["host_spans"].get("bench.step", 0)
    if not steps or not tr["collective_s"]:
        return None
    return 1e3 * tr["collective_exposed_s"] / steps
