"""The tied ternary head's share of its roofline in the decode step: the
least time the chip needs for the vocabulary head of the tokens received in
the traced window — the larger of 2 FLOPs per head weight per token over
the bf16 peak and, over HBM bandwidth, the 2-bit head once per
decode-executable run plus each token's f32 logits — over the device
self-time of the ops under the program's ``lm_head`` scope in the decode
executable. Moves ``output_tok_s``."""
from chipbench import counts, trace_names


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    runs, t = trace_names.decode_scope(red, "lm_head")
    (p0, _), (p1, _) = ctx["window"]
    tokens = sum(1 for t_, _, _ in ctx["records"]["tokens"] if p0 <= t_ < p1)
    if not runs or t <= 0 or not tokens:
        return None
    d, pk = ctx["dims"], ctx["peaks"]
    w = counts.head_params(d)
    need = max(2 * w * tokens / pk["bf16_flops"],
               (w // 4 * runs + d.vocab * counts.OUT_BYTES * tokens)
               / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
