"""experts_roofline: the least time the chip needs for the held
experts' grouped products in a train step, forward and backward, at the
expected pairs a MoE layer (the step's tokens x ``top_k * held /
n_experts``; FLOPs, and the rows and weights each product reads or
writes, ``bench/flops_moe.py``), over the device time of a step under the
``experts`` sub-scope (``experts_ms.train``), in %.  Moves
``train_tokens_per_s``."""
import flops
import flops_moe
import xsub


def read(ctx):
    ms = xsub.sub_ms(ctx, "experts")
    if not ms:
        return None
    c, cell = ctx["run"].counters, ctx["cell"]
    sz = cell.reference().sizes(cell.config)
    pairs = flops_moe.expected_pairs(sz, c["batch"] * c["seq"])
    least = flops.roofline_s(*flops_moe.experts_cost(sz, pairs),
                             ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
