"""The plain reference against the program at a tiny size on the CPU: the
engine's batched prefill and cached decode logits, with and without
tenants, agree with the reference's; the fp8 control does not."""
import numpy as np
import pytest

from chipbench.modelcfg import ROOT, load_config, reference
from chipbench.reference import Seq

TINY = ROOT / "tests" / "tiny"
SEED = 2 ** 31 + 77
#: Relative L2 error of the engine's logits against the reference's: the
#: engine computes in bf16 with an fp8 KV cache, the reference in f32. Read
#: at this size: the engine 0.023 (BitNet, tenants) and 0.016 (StarCoder2),
#: the fp8 control 0.069 and 0.057.
TOL = 4e-2


def _program_greedy(arch, dims, eng_cfg, seed, prompts, n_new):
    """Prefill + dense-cache decode through the program's own model, each
    step fed its own greedy token: (the sequences, the logits at every
    served position)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import build_engine
    eng = build_engine(arch, "tiny", slots=2, max_len=eng_cfg["max_len"],
                       prefill="batched", kv="dense", seed=seed,
                       n_adapters=eng_cfg.get("tenants", 0),
                       adapter_rank=eng_cfg.get("adapter_rank", 8),
                       adapter_budget_kb=eng_cfg.get("adapter_budget_kb"))
    model = eng.model
    seqs, out = [], []
    step = jax.jit(model.decode_step)
    for prompt, tenant in prompts:
        q = Seq(prompt, None, tenant)
        aidx = None
        if eng.adapters is not None:
            slot = (eng.adapters.acquire_versioned(f"tenant-{q.tenant}")[0]
                    if q.tenant is not None else 0)
            aidx = jnp.asarray([slot], jnp.int32)
        params = eng._effective_params()
        lg, cache = model.prefill(params, {"tokens": jnp.asarray(q.prompt)[None]},
                                  eng_cfg["max_len"], adapter_idx=aidx)
        rows = [np.asarray(lg[0], np.float32)[:dims.vocab]]
        for j in range(n_new - 1):
            tok = jnp.asarray([rows[-1].argmax()], jnp.int32)
            pos = jnp.asarray([len(q.prompt) + j], jnp.int32)
            lg, cache = step(params, cache, tok, pos, aidx)
            rows.append(np.asarray(lg[0], np.float32)[:dims.vocab])
        out.append(np.stack(rows))
        seqs.append(Seq(prompt, out[-1].argmax(-1).astype(np.int32), tenant))
    return seqs, out


def _rel(a, b):
    a, b = np.concatenate(a), np.concatenate(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("arch", ["bitnet-2b", "starcoder2-7b"])
def test_reference_agrees_with_prefill_and_cached_decode(arch):
    cfg = load_config(TINY / f"{arch}.json")
    dims, e = cfg["dims"], cfg["engine"]
    ref_mod = reference(cfg["reference"])
    rng = np.random.default_rng(3)
    tenants = e.get("tenants", 0)
    prompts = [(rng.integers(0, dims.vocab, 16 + 9 * i).astype(np.int32),
                (i % tenants) if tenants and i % 2 == 0 else None)
               for i in range(3)]
    seqs, prog = _program_greedy(arch, dims, e, SEED % (2 ** 31 - 1),
                                 prompts, 10)
    kw = dict(tenants=tenants, rank=e.get("adapter_rank", 8))
    seed, length = SEED % (2 ** 31 - 1), e["max_len"]
    ref = ref_mod.logits(dims, seed, seqs, length, **kw)
    ctl = ref_mod.logits(dims, seed, seqs, length, low=True, **kw)
    err, ctl_err = _rel(prog, ref), _rel(ctl, ref)
    assert err < TOL < ctl_err, (err, ctl_err)
    # a reference of the wrong seed misses by far more
    other = ref_mod.logits(dims, seed + 1, seqs, length, **kw)
    assert _rel(other, ref) > 0.5
    # greedy gaps: a token the program served is (near) the reference's best
    gaps = ref_mod.served_gaps(dims, seed, seqs, length, **kw)
    assert max(g.max() for g in gaps["gap"]) < 0.05
