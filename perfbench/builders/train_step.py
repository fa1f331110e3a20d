"""A training system through the normal path: ``LlamaForCausalLM`` +
``CompiledTrainStep`` (bf16 O2, AdamW + global-norm clip, fused
linear+CE, flash-aware ``core_attn`` remat), one chip.
"""
from __future__ import annotations

import time

from perfbench import reference
from perfbench.builders import models


def loss_fn(model, batch):
    return model(batch["input_ids"], labels=batch["labels"])


def build(cfg, traffic, seed, rec, rehearse, log):
    return TrainSystem(cfg, traffic, seed, rec, rehearse, log)


class TrainSystem:
    def __init__(self, cfg, traffic, seed, rec, rehearse, log):
        import paddle_tpu as paddle
        from paddle_tpu.jit.train import CompiledTrainStep

        self.sz = sz = cfg
        self.rec, self.log, self.seed = rec, log, seed
        self.seq, self.batch = traffic["seq_len"], traffic["batch"]
        self.vocab = sz["vocab_size"]
        self.chips = 1
        t0 = time.perf_counter()
        self.model = models.make_model(sz, seed, self.seq)
        t1 = time.perf_counter()
        # the forward is compared BEFORE the step exists: the step
        # donates the weights it is given
        self._check = self._forward_check()
        t2 = time.perf_counter()
        tr = sz["train"]
        opt = paddle.optimizer.AdamW(
            learning_rate=traffic["lr"],
            parameters=self.model.parameters(),
            grad_clip=paddle.ClipGradByGlobalNorm(tr["clip_global_norm"]))
        self.step = CompiledTrainStep(self.model, loss_fn, opt, seed=0)
        self.timing = {"weights_s": t1 - t0, "check_s": t2 - t1}
        log(f"train_step: {sz['name']} weights {t1 - t0:.1f}s, forward "
            f"check {t2 - t1:.1f}s {self._check}")

    def _forward_check(self) -> dict:
        """The program's forward (bf16, its kernels) on a seeded slice
        against the plain float32 reference's logits."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from paddle_tpu.jit.train import traced_forward

        n = min(self.sz["check_tokens"], self.seq)
        rng = np.random.default_rng(self.seed + 1)
        ids = rng.integers(0, self.vocab, size=(1, n), dtype=np.int32)
        model = self.model

        def fwd(params, ids):
            out = traced_forward(model, lambda m, b: m(b["input_ids"]),
                                 params, {"input_ids": ids},
                                 jax.random.key(0))
            return out[0].astype(jnp.float32)

        sd = model.raw_state_dict()
        got = np.asarray(jax.device_get(jax.jit(fwd)(sd, ids)))
        want = reference.logits(
            reference.canonical(self.sz["arch"], sd,
                                self.sz["num_hidden_layers"]),
            self.sz, ids[0].tolist())
        err = reference.rel_l2(got, want)
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        return {"ok": bool(err <= reference.LOGITS_REL_L2_TOL),
                "tokens": n, "rel_l2": err,
                "tolerance": reference.LOGITS_REL_L2_TOL,
                "argmax_agree": agree}

    def warm(self, plan):
        """The one step program: two steps on the first batch (the first
        compiles)."""
        import jax
        import numpy as np
        t0 = time.perf_counter()
        for _ in range(2):
            loss = float(np.asarray(jax.device_get(
                self.step(plan["pool"][0]))))
        self.timing["warmup_s"] = time.perf_counter() - t0
        self.log(f"train_step: two warm-up steps in "
                 f"{self.timing['warmup_s']:.1f}s, loss {loss:.4f}, "
                 f"step programs {self.step.step_compiles()}")

    def check(self) -> dict:
        return self._check

    def close(self):
        self.step.state = None
