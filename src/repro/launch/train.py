"""Training driver: QAT-from-scratch (the way BitNet-2B was made) or QLoRA
on-device tuning on the immutable packed base (C4).

Production posture: sharded params/optimizer over the mesh, fault-tolerant
step execution (runtime/), atomic async checkpoints with exact resume
(data cursor + RNG + step), straggler watchdog, optional cross-pod int8
gradient compression.

CPU-scale usage (the end-to-end example path):

    PYTHONPATH=src python -m repro.launch.train \
        --arch bitnet-2b --preset tiny --steps 200 --batch 8 --seq 256

Cluster usage: same entry point with --mesh data,model extents per pod; the
dry-run (dryrun.py) proves the production mesh compiles for every arch.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt_mod
from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import specs as specs_mod
from repro.launch import steps as steps_mod
from repro.models import sharding as shard_rules
from repro.models.transformer import Model
from repro.optim import AdamW, trainable_mask, warmup_cosine
from repro.runtime.fault import RetryPolicy, StepRunner


# ---------------------------------------------------------------------------
# Presets: reduced configs for CPU end-to-end runs
# ---------------------------------------------------------------------------


def reduce_config(cfg: ModelConfig, preset: str) -> ModelConfig:
    """Shrink an assigned architecture to a CPU-runnable size while keeping
    its family/topology (used by examples and smoke tests)."""
    if preset == "full":
        return cfg
    scale = {"tiny": 8, "small": 4}[preset]
    kw: Dict[str, Any] = dict(
        num_layers=max(2, cfg.num_layers // scale),
        d_model=max(128, cfg.d_model // scale),
        d_ff=max(256, cfg.d_ff // scale) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 2048),
        max_seq_len=min(cfg.max_seq_len, 4096),
    )
    if cfg.num_heads:
        kw["num_heads"] = max(2, cfg.num_heads // scale)
        # GQA requires Hq % Hkv == 0: pick the largest divisor of the reduced
        # head count that doesn't exceed the original kv-head count
        kv_cap = max(1, min(cfg.num_kv_heads, kw["num_heads"]))
        kw["num_kv_heads"] = max(d for d in range(1, kv_cap + 1)
                                 if kw["num_heads"] % d == 0)
        kw["head_dim"] = max(32, min(cfg.head_dim, kw["d_model"] // kw["num_heads"]))
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=max(4, cfg.moe.num_experts // scale),
            expert_d_ff=max(64, cfg.moe.expert_d_ff // scale),
            dense_d_ff=max(128, cfg.moe.dense_d_ff // scale) if cfg.moe.dense_d_ff else 0,
            dense_residual_d_ff=max(128, cfg.moe.dense_residual_d_ff // scale)
            if cfg.moe.dense_residual_d_ff else 0,
        )
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla,
            kv_lora_rank=max(32, cfg.mla.kv_lora_rank // scale),
            q_lora_rank=max(48, cfg.mla.q_lora_rank // scale),
            qk_nope_head_dim=max(16, cfg.mla.qk_nope_head_dim // scale),
            qk_rope_head_dim=max(16, cfg.mla.qk_rope_head_dim // scale),
            v_head_dim=max(16, cfg.mla.v_head_dim // scale),
        )
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm,
            state_size=max(16, cfg.ssm.state_size // scale),
            head_dim=max(16, cfg.ssm.head_dim // scale),
        )
    if cfg.block_pattern:
        n = kw["num_layers"]
        period = 3
        kw["block_pattern"] = "".join(
            "a" if (i % period) == period - 1 else "m" for i in range(n))
    return cfg.replace(**kw)


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainConfig:
    arch: str = "bitnet-2b"
    preset: str = "tiny"             # tiny | small | full
    mode: str = "qat"                # qat | qlora
    steps: int = 100                 # TOTAL schedule horizon (cosine anchor)
    stop_after: Optional[int] = None  # preemption point: stop (+ckpt) early
    batch: int = 8
    seq: int = 256
    lr: float = 3e-4
    warmup: int = 20
    seed: int = 0
    mesh_model: int = 1              # model-axis extent on the host mesh
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    resume: bool = True
    log_every: int = 10
    grad_compression: str = "none"   # none | int8  (cross-pod axis)
    data_path: Optional[str] = None  # mmap token file; None → synthetic


class Trainer:
    """Owns the mesh, sharded state, data pipeline, fault handling and the
    checkpoint lifecycle. One class serves the CPU examples and the cluster
    entry point — only the mesh differs."""

    def __init__(self, tc: TrainConfig):
        self.tc = tc
        base = get_config(tc.arch)
        self.cfg = reduce_config(base, tc.preset)
        self.mesh = mesh_mod.make_host_mesh(model=tc.mesh_model)
        shape = ShapeConfig("train", tc.seq, tc.batch, "train")

        self.model = Model(self.cfg, mode=tc.mode,
                           act_shard=steps_mod.act_sharding_for(self.mesh, shape))
        self.opt = AdamW(schedule=warmup_cosine(tc.lr, tc.warmup, tc.steps))

        pspecs = self.model.param_specs()
        self.p_shard = specs_mod.named(
            self.mesh,
            shard_rules.param_spec_tree(pspecs, self.mesh, mode=tc.mode, fsdp=True))

        if tc.mode == "qlora":
            # optimizer state exists only for the adapter leaves — the packed
            # ROM base is frozen (C4) and carries no moments at all.
            from repro.optim import partition
            self.mask = trainable_mask(pspecs, "qlora")
            train_specs, _ = partition(pspecs, self.mask)
            train_shard, _ = partition(self.p_shard, self.mask)
            _, self.o_shard = steps_mod._moment_shardings(
                train_specs, train_shard, self.opt, self.mesh)
            step = steps_mod.make_qlora_step(self.model, self.opt, self.mask)
        else:
            self.mask = None
            _, self.o_shard = steps_mod._moment_shardings(pspecs, self.p_shard,
                                                          self.opt, self.mesh)
            step = steps_mod.make_train_step(self.model, self.opt)
        batch_tree = specs_mod.train_inputs(self.cfg, shape)
        b_shard = specs_mod.batch_shardings(self.cfg, shape, self.mesh, batch_tree)
        self.step_fn = jax.jit(step,
                               in_shardings=(self.p_shard, self.o_shard, b_shard),
                               out_shardings=(self.p_shard, self.o_shard, None),
                               donate_argnums=(0, 1))

        self.data = TokenPipeline(DataConfig(
            vocab_size=self.cfg.vocab_size, batch=tc.batch, seq=tc.seq,
            seed=tc.seed, path=tc.data_path))
        self.runner = StepRunner(RetryPolicy())
        self.step = 0
        self._init_state()

    # -- state ---------------------------------------------------------------
    def _init_state(self):
        tc = self.tc
        with self.mesh:
            init = jax.jit(self.model.init, out_shardings=self.p_shard)
            self.params = init(jax.random.PRNGKey(tc.seed))
            if self.mask is not None:
                from repro.optim import partition
                opt_over, _ = partition(self.params, self.mask)
            else:
                opt_over = self.params
            self.opt_state = jax.jit(self.opt.init,
                                     out_shardings=self.o_shard)(opt_over)
        if tc.ckpt_dir and tc.resume:
            latest = ckpt_mod.latest_step(tc.ckpt_dir)
            if latest is not None:
                self.restore(latest)

    # -- checkpoint ------------------------------------------------------------
    def save(self, block: bool = False):
        if not self.tc.ckpt_dir:
            return
        state = {"params": self.params, "opt_state": self.opt_state}
        meta = {"step": self.step, "data_cursor": self.data.cursor,
                "arch": self.tc.arch, "preset": self.tc.preset}
        ckpt_mod.save(self.tc.ckpt_dir, self.step, state, meta, async_=not block)

    def restore(self, step: int):
        state = {"params": self.params, "opt_state": self.opt_state}
        state, meta = ckpt_mod.restore(self.tc.ckpt_dir, step, state,
                                       mesh=self.mesh)
        self.params, self.opt_state = state["params"], state["opt_state"]
        self.step = meta["step"]
        self.data.seek(meta["data_cursor"])
        print(f"[train] resumed from step {self.step}")

    # -- loop -------------------------------------------------------------------
    def run(self) -> Dict[str, float]:
        tc = self.tc
        last = {}
        t0 = time.time()
        stop_at = min(tc.steps, tc.stop_after or tc.steps)
        while self.step < stop_at:
            if self.runner.preemption.should_stop:
                print(f"[train] preemption at step {self.step}; checkpointing")
                break
            batch = self.data.next()

            def do_step():
                return self.step_fn(self.params, self.opt_state, batch)

            self.params, self.opt_state, metrics = self.runner.run(do_step)
            self.step += 1
            if self.step % tc.log_every == 0 or self.step == tc.steps:
                last = {k: float(v) for k, v in metrics.items()}
                dt = time.time() - t0
                tok_s = tc.batch * tc.seq * tc.log_every / max(dt, 1e-9)
                print(f"[train] step {self.step:5d} "
                      f"loss {last.get('ce_loss', last.get('loss', 0)):.4f} "
                      f"gnorm {last.get('grad_norm', 0):.3f} "
                      f"lr {last.get('lr', 0):.2e} "
                      f"| {tok_s:,.0f} tok/s")
                sys.stdout.flush()
                t0 = time.time()
            if tc.ckpt_dir and self.step % tc.ckpt_every == 0:
                self.save()
        self.save(block=True)
        ckpt_mod.wait_pending()
        return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="bitnet-2b")
    ap.add_argument("--preset", default="tiny", choices=("tiny", "small", "full"))
    ap.add_argument("--mode", default="qat", choices=("qat", "qlora"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    tc = TrainConfig(arch=args.arch, preset=args.preset, mode=args.mode,
                     steps=args.steps, batch=args.batch, seq=args.seq,
                     lr=args.lr, mesh_model=args.mesh_model,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     resume=not args.no_resume, seed=args.seed,
                     data_path=args.data)
    trainer = Trainer(tc)
    final = trainer.run()
    print("[train] done:", json.dumps(final))
    return 0


if __name__ == "__main__":
    sys.exit(main())
