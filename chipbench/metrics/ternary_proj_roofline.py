"""The ternary projections' share of their roofline in the decode step: the
least time the chip needs for the projections (q, k, v, o and the FFN) of
the tokens received in the traced window — the larger of their FLOPs (2 per
weight per token) over the bf16 peak and their bytes over HBM bandwidth,
the bytes being the 2-bit weights once per decode-executable run plus each
token's bf16 activations in and out — over the device self-time of the ops
under the program's ``ternary_proj`` scope in the decode executable.
Moves ``output_tok_s``."""
from chipbench import counts, trace_names


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    runs, t = trace_names.decode_scope(red, "ternary_proj")
    (p0, _), (p1, _) = ctx["window"]
    tokens = sum(1 for t_, _, _ in ctx["records"]["tokens"] if p0 <= t_ < p1)
    if not runs or t <= 0 or not tokens:
        return None
    d, pk = ctx["dims"], ctx["peaks"]
    shapes = [(d.d_model, d.q_dim), (d.d_model, d.kv_dim),
              (d.d_model, d.kv_dim), (d.q_dim, d.d_model),
              (d.d_model, d.d_ff), (d.d_ff, d.d_model)]
    io = d.layers * sum(k + n for k, n in shapes) * counts.ACT_BYTES
    w = counts.linear_params(d)
    need = max(2 * w * tokens / pk["bf16_flops"],
               (w // 4 * runs + io * tokens) / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
