"""The decode step's share of the chip's bf16 peak: the model FLOPs of the
tokens clients received in the traced window (projections, head, adapters
and attention at each token's own context) over the device time of the
decode executable in the trace. Moves ``output_tok_s``."""
from chipbench import counts, trace_reduce


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    t = trace_reduce.total(red["modules"], "decode_fn")
    if t <= 0:
        return None
    d, rank = ctx["dims"], ctx["engine"].get("adapter_rank", 8)
    (p0, _), (p1, _) = ctx["window"]
    base = 2 * counts.linear_params(d) + 2 * counts.head_params(d)
    lora = counts.adapter_flops_per_token(d, rank)
    flops = sum(base + counts.attn_flops(d, c) + (lora if ad else 0)
                for t_, c, ad in ctx["records"]["tokens"] if p0 <= t_ < p1)
    return 100.0 * flops / (t * ctx["peaks"]["bf16_flops"])
