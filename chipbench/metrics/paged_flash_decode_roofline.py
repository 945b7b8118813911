"""``paged_flash_decode``'s share of its roofline: the least time the chip
needs for the attention of the tokens received in the traced window (the
fp8 K/V of each token's context, its query and f32 output; 4·H·D FLOPs per
context position, per layer), the larger of FLOPs over peak and bytes over
HBM bandwidth, over the kernel's device time. Moves ``output_tok_s``."""
from chipbench import counts, trace_reduce


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    t = trace_reduce.total(red["ops"], "paged_flash_decode")
    if t <= 0:
        return None
    d, pk = ctx["dims"], ctx["peaks"]
    (p0, _), (p1, _) = ctx["window"]
    contexts = [c for t_, c, _ in ctx["records"]["tokens"] if p0 <= t_ < p1]
    if not contexts:
        return None
    flops, nbytes = counts.flash_decode_call(d, contexts)
    need = max(flops * d.layers / pk["bf16_flops"],
               nbytes * d.layers / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
