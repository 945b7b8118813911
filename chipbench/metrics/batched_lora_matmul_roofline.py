"""``batched_lora_matmul``'s share of its roofline: the least time the
chip needs for the low-rank q and v paths of the adapter-carrying tokens
received in the traced window (their bf16 rows, f32 outputs, and the 2-bit
A and B of every resident tenant once per call), over the kernel's device
time. Moves ``output_tok_s``."""
from chipbench import counts, trace_reduce


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"]:
        return None
    t = trace_reduce.total(red["ops"], "batched_lora_matmul")
    calls = sum(v for k, v in red["op_calls"].items()
                if "batched_lora_matmul" in k)
    if t <= 0 or not calls:
        return None
    d, pk, eng = ctx["dims"], ctx["peaks"], ctx["engine"]
    rank, tenants = eng.get("adapter_rank", 8), eng.get("tenants", 0)
    (p0, _), (p1, _) = ctx["window"]
    rows = sum(1 for t_, _, ad in ctx["records"]["tokens"]
               if ad and p0 <= t_ < p1)
    flops = nbytes = 0
    for n in (d.q_dim, d.kv_dim):         # q and v, one call each per layer
        f, b = counts.lora_call(d, rows * d.layers, d.d_model, n, rank, 0)
        _, ab = counts.lora_call(d, 0, d.d_model, n, rank, tenants)
        flops += f
        nbytes += b + ab * calls // 2
    need = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * need / t
