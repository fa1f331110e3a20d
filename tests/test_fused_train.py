"""Fused step regions (ops/pallas/fused_train) — bit-identity suite.

The fused train step's contract is NOT "close": flipping
``fused_step``/``fuse_norm_rope`` off must reproduce the same
trajectory bit-for-bit (params, slot state, losses), because the CPU
reference paths mirror the kernel math op-for-op.  This module pins:

* fused-vs-reference optimizer parity — AdamW (decoupled weight decay,
  beta correction, LR schedule), SGD, Momentum (plain + Nesterov),
  Adam with L2 decay, global-norm clip folded in, small-leaf packing
  with odd sizes, and the per-leaf fallback for unfused optimizers /
  per-tensor clips;
* the f32 global-norm accumulation guard for bf16 grads (nn/clip.py);
* fused add+RMSNorm / add+LayerNorm / matmul+rope chains == unfused,
  in forward AND eager backward;
* checkpoint interplay: fused slot state round-trips through
  save_checkpoint/load_checkpoint with a bit-identical resume, and
  fused checkpoints load into reference steps (same state tree);
* 2-way-mesh sharded parity with bucketed gradient collectives,
  including bucket-boundary edge cases;
* the one-compiled-program-per-step-path invariant, hapi plumbing, and
  a tier-1 runtime budget guard.
"""
import re
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit.train import CompiledTrainStep
from paddle_tpu.nn.clip import ClipGradByGlobalNorm, global_norm_sq_f32
from paddle_tpu.ops import _nn
from paddle_tpu.ops.pallas import fused_train as FT

from helpers import make_strategy


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class _Net(nn.Layer):
    """Small net with a long tail of sub-megabyte leaves (norm scales,
    biases) plus 2-D matmul weights — the packing path's natural diet."""

    def __init__(self, din=16, hidden=32, dout=8):
        super().__init__()
        self.fc1 = nn.Linear(din, hidden)
        self.norm = nn.LayerNorm(hidden)
        self.fc2 = nn.Linear(hidden, dout)

    def forward(self, x):
        return self.fc2(self.norm(paddle.nn.functional.relu(self.fc1(x))))


def _mse(model, batch):
    out = model(batch["x"])
    d = out - batch["y"]
    return (d * d).mean()


def _batches(steps, din=16, dout=8, batch=4, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((batch, din)).astype(np.float32),
             "y": rng.standard_normal((batch, dout)).astype(np.float32)}
            for _ in range(steps)]


def _tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def _run(make_model, make_opt, fused, steps=5, seed=3, bf16=False):
    paddle.seed(seed)
    model = make_model()
    if bf16:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = make_opt(model)
    step = CompiledTrainStep(model, _mse, opt, fused_step=fused)
    losses = [float(np.asarray(jax.device_get(step(b))))
              for b in _batches(steps)]
    return step, losses


def _parity(make_opt, steps=5, bf16=False, make_model=_Net):
    sf, lf = _run(make_model, make_opt, True, steps=steps, bf16=bf16)
    sr, lr = _run(make_model, make_opt, False, steps=steps, bf16=bf16)
    assert lf == lr, f"fused losses diverged: {lf} vs {lr}"
    assert _tree_equal(sf.state["params"], sr.state["params"])
    assert _tree_equal(sf.state["opt"], sr.state["opt"])
    return sf, sr


# ---------------------------------------------------------------------------
# fused optimizer parity
# ---------------------------------------------------------------------------

class TestFusedOptimizerParity:
    def test_adamw_decay_clip_schedule(self):
        """AdamW: decoupled weight decay + beta correction + LR schedule
        + global-norm clip, all folded into the fused pass."""
        def mk(m):
            sched = optimizer.lr.MultiStepDecay(learning_rate=1e-2,
                                                milestones=[2, 4],
                                                gamma=0.5)
            return optimizer.AdamW(learning_rate=sched, weight_decay=0.01,
                                   parameters=m.parameters(),
                                   grad_clip=ClipGradByGlobalNorm(1.0))
        _parity(mk, steps=6)

    def test_sgd_parity(self):
        _parity(lambda m: optimizer.SGD(learning_rate=0.05,
                                        parameters=m.parameters()))

    def test_momentum_parity_with_decay_and_clip(self):
        _parity(lambda m: optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, weight_decay=1e-4,
            parameters=m.parameters(),
            grad_clip=ClipGradByGlobalNorm(0.5)))

    def test_nesterov_momentum_parity(self):
        _parity(lambda m: optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, use_nesterov=True,
            parameters=m.parameters()))

    def test_adam_l2_decay_parity(self):
        """Adam (non-decoupled): L2 decay folds into the grad before the
        moment updates, exactly like apply_gradients."""
        _parity(lambda m: optimizer.Adam(
            learning_rate=1e-2, weight_decay=0.01,
            parameters=m.parameters()))

    def test_bf16_params_clip_roundtrip(self):
        """bf16 params/grads: the fused path must replay the clip's
        round-trip through the grad dtype to stay bit-identical."""
        _parity(lambda m: optimizer.AdamW(
            learning_rate=1e-2, weight_decay=0.01,
            parameters=m.parameters(),
            grad_clip=ClipGradByGlobalNorm(1.0)), steps=4, bf16=True)

    def test_packing_odd_sizes(self):
        """Small-leaf packing with awkward sizes (1, 7, 33, 129): the
        flat buffer concatenates, updates, and splits back exactly —
        bitwise equal to the per-leaf loop (eager: same ops on the same
        elements)."""
        rng = np.random.default_rng(8)
        params = {f"p{n}": jnp.asarray(rng.standard_normal(n),
                                       jnp.float32)
                  for n in (1, 7, 33, 129)}
        grads = {k: jnp.asarray(rng.standard_normal(v.shape),
                                jnp.float32) for k, v in params.items()}
        opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 weight_decay=1e-4, parameters=None,
                                 grad_clip=ClipGradByGlobalNorm(1.0))
        state = opt.init_state(params)
        pr, sr = opt.apply_gradients(params, grads, state, lr=0.05)
        pp, sp = opt.apply_gradients_fused(params, grads, state, lr=0.05,
                                           pack_small=True)
        assert _tree_equal(pr, pp)
        assert _tree_equal(sr, sp)

    def test_fallback_unfused_optimizer(self):
        """RMSProp has no fused kernel: apply_gradients_fused must fall
        back to the per-leaf reference loop (and stay equal)."""
        m = _Net()
        opt = optimizer.RMSProp(learning_rate=1e-2,
                                parameters=m.parameters())
        assert opt._fused_kind() is None
        _parity(lambda mm: optimizer.RMSProp(learning_rate=1e-2,
                                             parameters=mm.parameters()),
                steps=3)

    def test_fallback_per_tensor_clip(self):
        """ClipGradByNorm (per-tensor) has no fused folding — per-leaf
        fallback keeps parity."""
        from paddle_tpu.nn.clip import ClipGradByNorm
        _parity(lambda m: optimizer.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            grad_clip=ClipGradByNorm(0.5)), steps=3)

    def test_compile_count_invariant(self):
        """fused_step=True keeps ONE compiled program for the step path."""
        sf, _ = _run(_Net, lambda m: optimizer.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            grad_clip=ClipGradByGlobalNorm(1.0)), True, steps=5)
        assert sf.step_compiles() == 1

    def test_packed_mode_math_identity(self):
        """pack_small=True (the TPU kernel configuration) is the same
        math: bitwise equal op-by-op outside jit; under jit XLA may
        re-cluster fusions (FMA contraction at the last ulp), so the
        compiled comparison is allclose-tight, and the state tree
        structure is unchanged."""
        rng = np.random.default_rng(2)
        params = {"w": jnp.asarray(rng.standard_normal((16, 32)),
                                   jnp.float32),
                  "b": jnp.asarray(rng.standard_normal(32), jnp.float32)}
        grads = {k: jnp.asarray(rng.standard_normal(v.shape), jnp.float32)
                 for k, v in params.items()}
        opt = optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                              parameters=None,
                              grad_clip=ClipGradByGlobalNorm(1.0))
        state = opt.init_state(params)
        pr, sr = opt.apply_gradients(params, grads, state, lr=1e-2)
        pp, sp = opt.apply_gradients_fused(params, grads, state, lr=1e-2,
                                           pack_small=True)
        assert _tree_equal(pr, pp)          # eager: bit-identical
        assert _tree_equal(sr, sp)
        assert jax.tree_util.tree_structure(sr) \
            == jax.tree_util.tree_structure(sp)
        jp, js = jax.jit(lambda p, g, s: opt.apply_gradients_fused(
            p, g, s, lr=1e-2, pack_small=True))(params, grads, state)
        for k in params:
            np.testing.assert_allclose(np.asarray(jp[k]),
                                       np.asarray(pr[k]), rtol=0,
                                       atol=1e-8)

    def test_grad_accum_apply_grads_parity(self):
        """The accumulation path (grad_step + apply_grads) dispatches
        through the same fused update."""
        def accum(fused):
            paddle.seed(11)
            m = _Net()
            opt = optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                  parameters=m.parameters(),
                                  grad_clip=ClipGradByGlobalNorm(1.0))
            step = CompiledTrainStep(m, _mse, opt, fused_step=fused)
            for b1, b2 in zip(_batches(2, seed=1), _batches(2, seed=2)):
                _, g1 = step.grad_step(b1)
                _, g2 = step.grad_step(b2)
                acc = jax.tree_util.tree_map(lambda a, b: (a + b) / 2.0,
                                             g1, g2)
                step.apply_grads(acc)
            return step

        sf, sr = accum(True), accum(False)
        assert _tree_equal(sf.state["params"], sr.state["params"])
        assert _tree_equal(sf.state["opt"], sr.state["opt"])


# ---------------------------------------------------------------------------
# nn/clip.py f32 global-norm audit
# ---------------------------------------------------------------------------

class TestClipF32Accumulation:
    def test_bf16_grads_accumulate_in_f32(self):
        """4096 bf16 ones: a bf16-accumulated sum of squares saturates at
        256 (8 mantissa bits), under-reporting the norm 4x.  The f32
        helper must get exactly 64.0 — and it is the SAME definition the
        fused step uses for its clip scale."""
        g = jnp.ones((4097,), jnp.bfloat16)
        norm_sq = float(global_norm_sq_f32([g]))
        assert norm_sq == 4097.0
        # the failure mode the helper guards against: bf16's 8 mantissa
        # bits cannot represent 4097 — a bf16-kept accumulation rounds it
        assert float(jnp.asarray(4097.0).astype(jnp.bfloat16)) != 4097.0
        clip = ClipGradByGlobalNorm(1.0)
        assert float(clip.global_norm([g])) == float(jnp.sqrt(
            jnp.asarray(4097.0)))

    def test_helper_matches_f64_on_mixed_magnitudes(self):
        rng = np.random.default_rng(0)
        leaves = [jnp.asarray(rng.standard_normal(s).astype(np.float32)
                              * 300.0).astype(jnp.bfloat16)
                  for s in (17, 1024, 333)]
        got = float(global_norm_sq_f32(leaves))
        want = sum(float(np.sum(np.square(
            np.asarray(g, np.float32).astype(np.float64)))) for g in leaves)
        assert abs(got - want) / want < 1e-2

    def test_fused_clip_scale_uses_shared_helper(self):
        src = Path(paddle.optimizer.optimizer.__file__).read_text()
        assert "global_norm_sq_f32" in src, (
            "apply_gradients_fused must compute its clip scale through "
            "nn/clip.py's shared f32 helper")


# ---------------------------------------------------------------------------
# fused chains: add+RMSNorm, add+LayerNorm, matmul+rope
# ---------------------------------------------------------------------------

class TestFusedChains:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_add_rms_norm_matches_unfused(self, dtype):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 8, 64)),
                        jnp.float32).astype(dtype)
        res = jnp.asarray(rng.standard_normal((2, 8, 64)),
                          jnp.float32).astype(dtype)
        w = jnp.asarray(rng.standard_normal(64), jnp.float32).astype(dtype)
        h, y = FT.add_rms_norm_reference(x, res, w, 1e-6)
        h2 = res + x
        y2 = _nn.rms_norm(h2, w, epsilon=1e-6)
        assert np.array_equal(np.asarray(h), np.asarray(h2))
        assert np.array_equal(np.asarray(y), np.asarray(y2))

    @pytest.mark.parametrize("with_bias", [True, False])
    def test_add_layer_norm_matches_unfused(self, with_bias):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((3, 5, 32)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((3, 5, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(32), jnp.float32)
        b = jnp.asarray(rng.standard_normal(32), jnp.float32) \
            if with_bias else None
        h, y = FT.add_layer_norm_reference(x, res, w, b, 1e-5)
        h2 = res + x
        y2 = _nn.layer_norm(h2, [32], w, b, epsilon=1e-5)
        assert np.array_equal(np.asarray(h), np.asarray(h2))
        assert np.array_equal(np.asarray(y), np.asarray(y2))

    @pytest.mark.parametrize("interleaved", [False, True])
    def test_matmul_rope_matches_linear_rope(self, interleaved):
        from paddle_tpu.models.llama import (_apply_rope_raw,
                                             _rope_cos_sin)
        rng = np.random.default_rng(3)
        b, s, hidden, heads, hd = 2, 8, 32, 2, 16
        x = jnp.asarray(rng.standard_normal((b, s, hidden)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((hidden, heads * hd)),
                        jnp.float32)
        emb = _rope_cos_sin(s, hd, 10000.0)
        cos, sin = jnp.cos(jnp.asarray(emb)), jnp.sin(jnp.asarray(emb))
        got = FT.matmul_rope_reference(x, w, cos, sin, heads, hd,
                                       interleaved)
        y = _nn.linear(x, w).reshape(b, s, heads, hd)
        want, _ = _apply_rope_raw(y, y, cos, sin, interleaved=interleaved)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_qkv_rope_matches_unfused_chain(self):
        from paddle_tpu.models.llama import (_apply_rope_raw,
                                             _rope_cos_sin)
        rng = np.random.default_rng(4)
        b, s, hidden, heads, nkv, hd = 2, 8, 32, 4, 2, 8
        x = jnp.asarray(rng.standard_normal((b, s, hidden)), jnp.float32)
        wq = jnp.asarray(rng.standard_normal((hidden, heads * hd)),
                         jnp.float32)
        wk = jnp.asarray(rng.standard_normal((hidden, nkv * hd)),
                         jnp.float32)
        wv = jnp.asarray(rng.standard_normal((hidden, nkv * hd)),
                         jnp.float32)
        emb = _rope_cos_sin(s, hd, 10000.0)
        cos, sin = jnp.cos(jnp.asarray(emb)), jnp.sin(jnp.asarray(emb))
        q, k, v = FT.qkv_rope_raw(x, wq, wk, wv, cos, sin, n_heads=heads,
                                  n_kv=nkv, head_dim=hd)
        q2 = _nn.linear(x, wq).reshape(b, s, heads, hd)
        k2 = _nn.linear(x, wk).reshape(b, s, nkv, hd)
        v2 = _nn.linear(x, wv).reshape(b, s, nkv, hd)
        q2, k2 = _apply_rope_raw(q2, k2, cos, sin)
        for got, want in ((q, q2), (k, k2), (v, v2)):
            assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_llama_fuse_flag_off_bit_identical(self):
        """fuse_norm_rope=True (default) vs False: one full train step,
        identical loss and updated params."""
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)

        def run(flag):
            cfg = llama_tiny_config()
            cfg.fuse_norm_rope = flag
            paddle.seed(21)
            m = LlamaForCausalLM(cfg)
            opt = optimizer.AdamW(learning_rate=1e-3,
                                  parameters=m.parameters(),
                                  grad_clip=ClipGradByGlobalNorm(1.0))
            step = CompiledTrainStep(
                m, lambda mm, b: mm(b["ids"], labels=b["lab"]), opt)
            rng = np.random.default_rng(5)
            ids = rng.integers(0, 256, size=(2, 16), dtype=np.int32)
            lab = np.concatenate(
                [ids[:, 1:], np.full((2, 1), -100, np.int32)], axis=1)
            loss = float(np.asarray(jax.device_get(
                step({"ids": ids, "lab": lab}))))
            return loss, step.state["params"]

        loss_f, params_f = run(True)
        loss_u, params_u = run(False)
        assert loss_f == loss_u
        assert _tree_equal(params_f, params_u)

    def test_transformer_postnorm_fused_matches_manual(self):
        """Post-norm TransformerEncoderLayer: the fused residual→norm
        chains equal the hand-composed unfused math."""
        paddle.seed(9)
        layer = nn.TransformerEncoderLayer(32, 4, 64, dropout=0.0,
                                           normalize_before=False)
        layer.eval()
        x = paddle.to_tensor(
            np.random.default_rng(6).standard_normal(
                (2, 5, 32)).astype(np.float32))
        got = layer(x)
        # unfused twin, composed from the same submodules
        attn = layer.self_attn(x, x, x, None)
        h = x + layer.dropout1(attn)
        src = layer.norm1(h)
        ff = layer.linear2(layer.dropout(
            layer.activation(layer.linear1(src))))
        want = layer.norm2(src + layer.dropout2(ff))
        assert np.array_equal(got.numpy(), want.numpy())

    def test_forward_residual_eager_backward(self):
        """Eager autograd flows through the fused chain's two outputs and
        matches the unfused composition's grads bitwise."""
        rng = np.random.default_rng(7)
        xv = rng.standard_normal((4, 64)).astype(np.float32)
        rv = rng.standard_normal((4, 64)).astype(np.float32)
        paddle.seed(13)
        norm = nn.RMSNorm(64)

        def run(fused):
            x = paddle.to_tensor(xv, stop_gradient=False)
            r = paddle.to_tensor(rv, stop_gradient=False)
            if fused:
                h, y = norm.forward_residual(x, r)
            else:
                h = r + x
                y = norm(h)
            ((y * y).sum() + (h * h).sum()).backward()
            return x.grad.numpy(), r.grad.numpy()

        gx_f, gr_f = run(True)
        gx_u, gr_u = run(False)
        # the EAGER tape composes one fused vjp node vs two chained
        # nodes — cotangent contributions accumulate in a different
        # order, so eager grads agree to float tolerance, not bitwise
        # (the compiled path traces identical jaxprs either way and IS
        # bitwise — test_llama_fuse_flag_off_bit_identical)
        np.testing.assert_allclose(gx_f, gx_u, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(gr_f, gr_u, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# checkpoint interplay
# ---------------------------------------------------------------------------

class TestCheckpointInterplay:
    def _mk_step(self, fused=True):
        paddle.seed(31)
        m = _Net()
        opt = optimizer.AdamW(
            learning_rate=optimizer.lr.MultiStepDecay(
                learning_rate=1e-2, milestones=[3], gamma=0.1),
            weight_decay=0.01, parameters=m.parameters(),
            grad_clip=ClipGradByGlobalNorm(1.0))
        return CompiledTrainStep(m, _mse, opt, fused_step=fused)

    def test_fused_resume_bit_identical(self, tmp_path):
        """save at step 4, restore into a FRESH fused step, continue —
        the loss trajectory and final state match the uninterrupted run
        exactly (slot moments, Adam step counter, LR schedule)."""
        batches = _batches(7, seed=17)
        straight = self._mk_step()
        losses_straight = [float(np.asarray(jax.device_get(straight(b))))
                           for b in batches]

        first = self._mk_step()
        losses = [float(np.asarray(jax.device_get(first(b))))
                  for b in batches[:4]]
        first.save_checkpoint(str(tmp_path / "ck"))

        resumed = self._mk_step()
        resumed.load_checkpoint(str(tmp_path / "ck"))
        losses += [float(np.asarray(jax.device_get(resumed(b))))
                   for b in batches[4:]]
        assert losses == losses_straight
        assert _tree_equal(resumed.state["params"],
                           straight.state["params"])
        assert _tree_equal(resumed.state["opt"], straight.state["opt"])

    def test_fused_checkpoint_loads_into_reference_step(self, tmp_path):
        """Fused and reference steps share one state-tree layout: a
        checkpoint written by either loads into the other, and the
        trajectories stay identical afterwards."""
        batches = _batches(5, seed=23)
        fused = self._mk_step(fused=True)
        for b in batches[:3]:
            fused(b)
        fused.save_checkpoint(str(tmp_path / "ck"))

        ref = self._mk_step(fused=False)
        ref.load_checkpoint(str(tmp_path / "ck"))
        assert _tree_equal(ref.state["params"], fused.state["params"])
        la = [float(np.asarray(jax.device_get(fused(b))))
              for b in batches[3:]]
        lb = [float(np.asarray(jax.device_get(ref(b))))
              for b in batches[3:]]
        assert la == lb


# ---------------------------------------------------------------------------
# sharded: bucketed gradient collectives on a 2-way mesh
# ---------------------------------------------------------------------------

class TestShardedBuckets:
    def _sharded(self, fused=True, bucket_mb=4.0, steps=5, stage=1):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.trainer import ShardedTrainStep
        fleet.init(strategy=make_strategy(dp=2))
        paddle.seed(41)
        m = _Net()
        opt = optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                              parameters=m.parameters(),
                              grad_clip=ClipGradByGlobalNorm(1.0))
        step = ShardedTrainStep(m, _mse, opt, stage=stage,
                                fused_step=fused,
                                grad_bucket_mb=bucket_mb)
        losses = [float(np.asarray(jax.device_get(step(b))))
                  for b in _batches(steps, seed=29)]
        return step, losses

    def test_bucket_plan_edge_cases(self):
        """Tiny budget: every replicated leaf lands in exactly one
        bucket; a leaf bigger than the whole budget gets its own; the
        trailing partial bucket still flushes."""
        step, _ = self._sharded(steps=1, bucket_mb=1.0 / 1024)  # 1 KB
        plan = step.grad_buckets()
        flat_p = jax.tree_util.tree_leaves(step.state["params"])
        covered = [i for b in plan for i in b]
        assert len(covered) == len(set(covered))
        assert covered, "dp mesh: replicated grads must be bucketed"
        budget = step._bucket_bytes
        for bucket in plan:
            sizes = [flat_p[i].size * flat_p[i].dtype.itemsize
                     for i in bucket]
            if len(bucket) == 1:
                continue
            assert sum(sizes) <= budget
            assert all(s < budget for s in sizes)
        big = [b for b in plan
               if len(b) == 1 and flat_p[b[0]].size
               * flat_p[b[0]].dtype.itemsize >= budget]
        assert big, "a giant leaf must claim a bucket of its own"

    def test_sharded_fused_vs_reference_bit_identical(self):
        _, lf = self._sharded(fused=True)
        _, lr = self._sharded(fused=False)
        assert lf == lr

    def test_bucketing_identity(self):
        """Bucket packing is concat→constraint→split: values must not
        change with bucketing off (or with a different bucket size)."""
        _, l_on = self._sharded(bucket_mb=1.0 / 1024, steps=3)
        _, l_off = self._sharded(bucket_mb=0.0, steps=3)
        _, l_mid = self._sharded(bucket_mb=4.0, steps=3)
        assert l_on == l_off == l_mid

    def test_sharded_compile_count(self):
        step, _ = self._sharded(steps=4)
        assert step.step_compiles() == 1


# ---------------------------------------------------------------------------
# hapi plumbing + budget guard
# ---------------------------------------------------------------------------

def test_hapi_prepare_fused_step_flag():
    from paddle_tpu.hapi import Model
    paddle.seed(1)
    m = Model(_Net())
    m.prepare(optimizer=optimizer.AdamW(
        learning_rate=1e-3, parameters=m.network.parameters()),
        loss=nn.MSELoss())
    assert m._ensure_train_step()._fused_step is True
    m.prepare(optimizer=optimizer.AdamW(
        learning_rate=1e-3, parameters=m.network.parameters()),
        loss=nn.MSELoss(), fused_step=False)
    assert m._ensure_train_step()._fused_step is False


def test_tier1_budget_guard():
    """This module must stay cheap on the tier-1 box: every test here
    uses toy shapes and no subprocesses."""
    here = Path(__file__).resolve().parent
    body = (here / "test_fused_train.py").read_text()
    n_fast = 0
    for mm in re.finditer(r"((?:@[\w.]+(?:\(.*?\))?\s*\n)*)"
                          r"    def (test_\w+)\(|^def (test_\w+)\(",
                          body, re.M):
        if "pytest.mark.slow" not in (mm.group(1) or ""):
            n_fast += 1
    assert n_fast <= 32, (
        f"{n_fast} fast fused-train tests — move heavy ones behind "
        f"@pytest.mark.slow to protect the 870 s tier-1 budget")
