"""mfu.train_moe: model FLOP/s utilisation of the MoE train step in the
traced window, in % of the chips' bf16 peak: the tokens of the steps
dispatched and finished inside the window (``bench.step`` spans) over its
length, times ``6 N_active + 6 L S H (d_qk + d_v)`` FLOPs per token, with
the held routed experts at ``top_k * held / n_experts`` pairs a token
(``bench/flops_moe.py``; recomputation not counted), over chips x peak.
Moves ``train_tokens_per_s``."""
import flops_moe


def read(ctx):
    c, cell, tr = ctx["run"].counters, ctx["cell"], ctx["trace"]
    steps = tr["host_spans"].get("bench.step", 0)
    if not steps:
        return None
    sz = cell.reference().sizes(cell.config)
    tokens_per_s = steps * c["batch"] * c["seq"] / tr["window_s"]
    return (100.0 * tokens_per_s * flops_moe.train_flops_per_token(
        sz, c["seq"]) / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
