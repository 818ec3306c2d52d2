"""paged_packed_attention_roofline: the least time the chip needs for the
packed paged-attention kernel's work in the traced ticks (FLOPs of each
token's causal context, and bytes of each segment's K/V pages read once,
``bench/flops.py``) over the kernel's summed device time in the trace,
in %.  Moves ``itl_p95_ms``."""
import flops

#: what the trace prints for the kernel's custom call
KERNEL = "paged_packed_attention"


def read(ctx):
    t = ctx["trace"]["kernel_s"].get(KERNEL)
    c = ctx["run"].counters
    if not t or not c["attn_flops"]:
        return None
    return 100.0 * flops.roofline_s(c["attn_flops"], c["attn_bytes"],
                                    ctx["peaks"]) / t
