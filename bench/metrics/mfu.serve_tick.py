"""mfu.serve_tick: model FLOPs of the live tokens the window's ticks
computed (``bench/flops.py``: blocks, causal attention, head over the rows
that yielded a token) over the summed wall time of the window's
``engine.step`` calls times the chip's bf16 peak, in %.  Waiting for
arrivals is left out, so it follows the tick and not the offered rate.
Moves ``itl_p95_ms``."""


def read(ctx):
    c = ctx["run"].counters
    if not c["step_s"]:
        return None
    return (100.0 * c["model_flops"]
            / (c["step_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
