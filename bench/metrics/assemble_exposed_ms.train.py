"""assemble_exposed_ms.train: milliseconds per train step in which an op
under the program's ``assemble`` layer scope (the tensor-parallel
all-reduces of ``blocks._assemble`` and their transposes) ran on a chip
and no op outside it did, averaged over the chips (``bench/xscope.py``).
Read only where the step has such ops.  Moves ``train_tokens_per_s``."""
import xscope


def read(ctx):
    got = xscope.per_unit(ctx)
    if got is None or "assemble" not in got[0]["scope_s"]:
        return None
    return 1e3 * got[0]["assemble_exposed_s"] / got[1]
