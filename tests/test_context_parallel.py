"""Context-parallel attention tests (VERDICT item 2 acceptance).

Ring + Ulysses over the sep axis must match full attention — forward
AND gradients — on the 8-virtual-device CPU mesh, at sep=2 and sep=4,
with and without GQA, causal and bidirectional.  Plus the model-level
path: Llama training with sep>1 matches the serial run.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import optimizer
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.context_parallel import sep_attention_raw
from paddle_tpu.ops import _nn


from helpers import make_strategy


def _qkv(b=2, s=32, h=4, hk=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _check(impl, causal, strategy, qkv_kwargs=None, tol=1e-4):
    fleet.init(strategy=strategy)
    q, k, v = _qkv(**(qkv_kwargs or {}))
    rng = np.random.default_rng(99)
    w = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    def loss_cp(q, k, v):
        return jnp.sum(sep_attention_raw(q, k, v, causal=causal,
                                         impl=impl) * w)

    def loss_ref(q, k, v):
        return jnp.sum(_nn.scaled_dot_product_attention(
            q, k, v, is_causal=causal) * w)

    out_cp = jax.jit(lambda a, b_, c: sep_attention_raw(
        a, b_, c, causal=causal, impl=impl))(q, k, v)
    out_ref = _nn.scaled_dot_product_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out_cp), np.asarray(out_ref),
                               rtol=tol, atol=tol)

    g_cp = jax.jit(jax.grad(loss_cp, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_cp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5 * tol, atol=5 * tol)


class TestRingAttention:
    def test_sep2_causal(self):
        _check("ring", True, make_strategy(sep=2))

    def test_sep4_causal(self):
        _check("ring", True, make_strategy(sep=4))

    def test_sep4_bidirectional(self):
        _check("ring", False, make_strategy(sep=4))

    def test_sep2_gqa(self):
        _check("ring", True, make_strategy(sep=2),
               qkv_kwargs=dict(h=8, hk=2))

    def test_sep4_with_dp_and_mp(self):
        # full hybrid: dp2 x sep2 x mp2 — batch/seq/head axes all manual
        _check("ring", True, make_strategy(dp=2, sep=2, mp=2),
               qkv_kwargs=dict(b=4, h=4, hk=4))


class TestUlyssesAttention:
    def test_sep2_causal(self):
        _check("ulysses", True, make_strategy(sep=2))

    def test_sep4_causal(self):
        _check("ulysses", True, make_strategy(sep=4))

    def test_sep2_gqa(self):
        _check("ulysses", True, make_strategy(sep=2),
               qkv_kwargs=dict(h=8, hk=2))

    def test_sep2_bidirectional(self):
        _check("ulysses", False, make_strategy(sep=2))


class TestAutoDispatch:
    def test_auto_prefers_ulysses_else_ring(self):
        fleet.init(strategy=make_strategy(sep=4))
        q, k, v = _qkv(h=4, hk=2)  # hk=2 not divisible by 4 -> ring
        out = sep_attention_raw(q, k, v, causal=True)  # impl=auto
        ref = _nn.scaled_dot_product_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_indivisible_seq_raises(self):
        fleet.init(strategy=make_strategy(sep=4))
        q, k, v = _qkv(s=30)
        with pytest.raises(NotImplementedError):
            sep_attention_raw(q, k, v, causal=True)


class TestModelLevelSep:
    def test_llama_sep_training_parity(self):
        """Llama tiny trained on (dp2, sep2, mp2) — attention routed
        through the sep path by F.scaled_dot_product_attention — must
        match the serial run (the reference's serial-vs-parallel loss
        parity pattern)."""
        from paddle_tpu.distributed.trainer import ShardedTrainStep
        from paddle_tpu.jit.train import CompiledTrainStep
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             LlamaPretrainingCriterion,
                                             llama_tiny_config)

        cfg = llama_tiny_config()
        cfg.sequence_parallel = True
        cfg.fuse_linear_cross_entropy = False

        def batches(steps, seed=0):
            rng = np.random.default_rng(seed)
            out = []
            for _ in range(steps):
                ids = ((np.arange(33)[None, :] +
                        rng.integers(0, 8, (4, 1))) % 64).astype(np.int32)
                out.append({"x": ids[:, :-1],
                            "y": ids[:, 1:].astype(np.int64)})
            return out

        crit = LlamaPretrainingCriterion()

        paddle.seed(42)
        model_ref = LlamaForCausalLM(cfg)
        opt_ref = optimizer.AdamW(learning_rate=1e-3)
        step_ref = CompiledTrainStep(
            model_ref, lambda m, b: crit(m(b["x"]), b["y"]), opt_ref, seed=0)
        losses_ref = [float(step_ref(b)) for b in batches(6)]

        fleet.init(strategy=make_strategy(dp=2, sep=2, mp=2))
        paddle.seed(42)
        model_cp = LlamaForCausalLM(cfg)
        opt_cp = optimizer.AdamW(learning_rate=1e-3)
        step_cp = ShardedTrainStep(
            model_cp, lambda m, b: crit(m(b["x"]), b["y"]), opt_cp,
            stage=1, seed=0)
        losses_cp = [float(step_cp(b)) for b in batches(6)]

        np.testing.assert_allclose(losses_ref, losses_cp, rtol=2e-3,
                                   atol=2e-3)
        assert losses_cp[-1] < losses_cp[0]


def test_cp_flash_backward_parity_on_tpu():
    """Real-chip parity of the ring backward's Pallas chunk kernels
    (diag + full blocks with global statistics) vs the f32 einsum
    oracle — runs tests/cp_bwd_check.py as a child that asks for the
    ``tpu`` platform (a chip belongs to one process: this pytest parent
    is pinned to the CPU and holds none).  Skips where the child finds
    no TPU, as in the sandbox."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "tpu"
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "cp_bwd_check.py")
    # bounded pre-probe: don't spend the worker's 580 s finding out
    # that there is no chip
    try:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            env=env, capture_output=True, text=True, timeout=75)
    except subprocess.TimeoutExpired:
        pytest.skip("TPU backend probe timed out")
    if probe.returncode != 0 or probe.stdout.strip() != "tpu":
        pytest.skip("no TPU backend reachable")
    proc = subprocess.run([sys.executable, worker], env=env,
                          capture_output=True, text=True, timeout=580)
    if proc.returncode == 86:
        pytest.skip("no TPU backend reachable")
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("{")][-1])
    assert res["parity"]["diag"]["max_rel_err"] < 5e-2
    assert res["parity"]["full"]["max_rel_err"] < 5e-2
