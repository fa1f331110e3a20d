"""Flash-attention Pallas kernel vs jnp oracle (fwd + grads).

Runs in Mosaic interpret mode on the CPU test platform (conftest pins
cpu); the same kernel compiles for real on TPU.  Mirrors the reference's
OpTest pattern: fused kernel vs reference impl, analytic grads compared.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw


def _oracle(q, k, v, causal):
    b, sq, h, d = q.shape
    hk = k.shape[2]
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    if causal:
        sk = kt.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _run(fn, *args):
    with pltpu.force_tpu_interpret_mode():
        return fn(*args)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hk", [4, 2])
def test_forward_matches_oracle(causal, hk):
    rng = np.random.default_rng(0)
    b, s, h, d = 2, 256, 4, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    got = _run(functools.partial(flash_attention_raw, causal=causal),
               q, k, v)
    want = _oracle(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal):
    rng = np.random.default_rng(1)
    b, s, h, hk, d = 1, 128, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)

    def loss_kernel(q, k, v):
        out = flash_attention_raw(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    def loss_oracle(q, k, v):
        out = _oracle(q, k, v, causal)
        return jnp.sum(out * jnp.cos(out))

    g_got = _run(jax.grad(loss_kernel, argnums=(0, 1, 2)), q, k, v)
    g_want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk", [(64, 256), (8, 128)])
def test_causal_decode_offset(sq, sk):
    """Causal sq<sk: Q rows are the LAST sq positions (chunked prefill /
    KV-cache decode)."""
    rng = np.random.default_rng(2)
    b, h, hk, d = 1, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sk, hk, d)), jnp.float32)
    got = _run(functools.partial(flash_attention_raw, causal=True), q, k, v)
    want = _oracle(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def loss_kernel(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention_raw(q, k, v, causal=True)))

    def loss_oracle(q, k, v):
        return jnp.sum(jnp.tanh(_oracle(q, k, v, True)))

    g_got = _run(jax.grad(loss_kernel, argnums=(0, 1, 2)), q, k, v)
    g_want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for got_g, want_g, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   atol=5e-5, rtol=5e-5, err_msg=f"d{name}")


def test_unsupported_shapes_raise():
    q = jnp.zeros((1, 64, 4, 32))  # d=32 not MXU-tileable
    with pytest.raises(NotImplementedError):
        flash_attention_raw(q, q, q, causal=False)
    q = jnp.zeros((1, 64, 4, 128))
    k = jnp.zeros((1, 32, 4, 128))  # causal sq > sk undefined
    with pytest.raises(NotImplementedError):
        flash_attention_raw(q, k, k, causal=True)


def _oracle_masked(q, k, v, mask, causal):
    b, sq, h, d = q.shape
    hk = k.shape[2]
    if hk != h:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    s = s + mask.astype(jnp.float32)
    if causal:
        sk = kt.shape[2]
        rows = jnp.arange(sq)[:, None] + (sk - sq)
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(rows >= cols, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.swapaxes(o, 1, 2)


@pytest.mark.parametrize("mask_shape", [(2, 1, 1, 64), (1, 1, 64, 64),
                                        (2, 4, 64, 64)])
def test_flash_masked_fwd_matches_oracle(mask_shape):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 64, 4, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 64)).astype(np.float32))
    # padding-style additive mask: random -inf entries
    mask = jnp.asarray(np.where(
        rng.uniform(size=mask_shape) < 0.25, -1e30, 0.0
    ).astype(np.float32))
    with pltpu.force_tpu_interpret_mode():
        got = flash_attention_raw(q, k, v, causal=False, mask=mask)
    want = _oracle_masked(q, k, v, mask, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_masked_grads_match_oracle():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 64)).astype(np.float32))
    mask = jnp.asarray(np.where(
        rng.uniform(size=(1, 1, 1, 32)) < 0.3, -1e30, 0.0
    ).astype(np.float32))

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention_raw(q, k, v, causal=True,
                                           mask=mask) ** 2)

    def loss_oracle(q, k, v):
        return jnp.sum(_oracle_masked(q, k, v, mask, causal=True) ** 2)

    with pltpu.force_tpu_interpret_mode():
        g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


def test_flash_gqa_bwd_outputs_kv_head_granular():
    """The dK/dV kernel writes [B, KVH, S, D] directly (no group-times
    materialize+sum)."""
    from paddle_tpu.ops.pallas.flash_attention import _bwd_impl, _fwd
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 8, 32, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 64)).astype(np.float32))
    do = jnp.ones((1, 8, 32, 64), jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        out, lse = _fwd(q, k, v, causal=False, bq=32, bk=32)
        dq, dk, dv = _bwd_impl(q, k, v, out, lse, do, causal=False,
                               bq=32, bk=32)
    assert dk.shape == (1, 2, 32, 64)
    assert dv.shape == (1, 2, 32, 64)


# ---------------------------------------------------------------------------
# round 3: in-kernel dropout + trainable-bias gradients
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="interpret mode stubs prng_random_bits to zeros (jax 0.9) — "
           "dropout randomness validated on the real v5e in round 3: "
           "seeds differ, mean-preserving, exact-mask grad parity")
def test_dropout_deterministic_and_mean_preserving():
    rng = np.random.default_rng(5)
    b, s, h, d = 1, 256, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    seed = jnp.int32(42)
    f = functools.partial(flash_attention_raw, causal=False,
                          dropout_p=0.5)
    o1 = _run(functools.partial(f, seed=seed), q, k, v)
    o2 = _run(functools.partial(f, seed=seed), q, k, v)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = _run(functools.partial(f, seed=jnp.int32(7)), q, k, v)
    assert float(jnp.abs(o1 - o3).max()) > 1e-3   # different mask
    base = _run(functools.partial(flash_attention_raw, causal=False),
                q, k, v)
    assert float(jnp.abs(o1 - base).max()) > 1e-3  # dropout did drop
    # E[dropout(P)] = P: averaging many seeds approaches the dense out
    outs = [
        _run(functools.partial(f, seed=jnp.int32(i)), q, k, v)
        for i in range(8)]
    avg = sum(np.asarray(o, np.float64) for o in outs) / len(outs)
    err = np.abs(avg - np.asarray(base, np.float64)).mean()
    scale = np.abs(np.asarray(base)).mean()
    assert err < 0.35 * scale, (err, scale)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="interpret mode stubs prng_random_bits (see above)")
def test_dropout_grads_consistent_with_forward():
    """Extract the forward's actual dropout mask (identity-V trick:
    out rows become the dropped prob matrix), then check the kernel's
    analytic grads against a dense oracle using that EXACT mask —
    proves the backward kernels regenerate the same mask.  (Validated
    on v5e in the round-3 session: all grads within 1%.)"""
    rng = np.random.default_rng(6)
    b, s, h, d = 1, 64, 1, 128
    p_drop = 0.5
    seed = jnp.int32(3)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    eyeV = jnp.zeros((b, s, h, d), jnp.float32).at[0, :, 0, :s].set(
        jnp.eye(s))
    out_eye = flash_attention_raw(q, k, eyeV, causal=False,
                                  dropout_p=p_drop, seed=seed)
    mask = jnp.asarray(np.asarray(out_eye[0, :, 0, :s]) > 1e-12)
    W = jnp.asarray(rng.standard_normal((s, d)), jnp.float32)

    def loss_k(q, k, v):
        out = flash_attention_raw(q, k, v, causal=False,
                                  dropout_p=p_drop, seed=seed)
        return jnp.sum(out[0, :, 0, :] * W)

    def loss_o(q, k, v):
        sc = (q[0, :, 0, :] @ k[0, :, 0, :].T
              / jnp.sqrt(jnp.float32(d)))
        p = jax.nn.softmax(sc, axis=-1)
        out = (jnp.where(mask, p, 0.0) / (1 - p_drop)) @ v[0, :, 0, :]
        return jnp.sum(out * W)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_o, argnums=(0, 1, 2))(q, k, v)
    for name, a, bb in zip("qkv", gk, go):
        scale = float(jnp.abs(bb).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=0.02 * scale,
                                   err_msg=f"d{name}")


def test_trainable_bias_grads_match_oracle():
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_raw_ext
    rng = np.random.default_rng(7)
    b, s, h, d = 2, 128, 4, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)

    for mshape in [(1, h, s, s), (b, 1, s, s), (1, 1, s, s),
                   (b, h, s, s)]:
        bias = jnp.asarray(rng.standard_normal(mshape) * 0.5,
                           jnp.float32)

        def loss_kernel(bias, q, k, v):
            out = flash_attention_raw_ext(
                q, k, v, bias, jnp.zeros((), jnp.int32), causal=True,
                mask_grad=True)
            return jnp.sum(out * jnp.cos(out))

        def loss_oracle(bias, q, k, v):
            qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
            kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
            vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
            sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
            sc = sc + bias
            mask = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(mask, sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            out = jnp.swapaxes(
                jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)
            return jnp.sum(out * jnp.cos(out))

        g = _run(jax.grad(loss_kernel, argnums=(0, 1)), bias, q, k, v)
        gw = jax.grad(loss_oracle, argnums=(0, 1))(bias, q, k, v)
        np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gw[0]),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"dbias {mshape}")
        np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gw[1]),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"dq {mshape}")


def test_sdpa_trainable_bias_gets_real_grads():
    """F.scaled_dot_product_attention with a trainable bias Tensor: the
    bias gradient is real (kernel dmask path), matching the jnp path."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.nn import functional as F

    rng = np.random.default_rng(8)
    b, s, h, d = 1, 64, 2, 64
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype("float32"))
    k = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype("float32"))
    v = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype("float32"))
    bias_np = (rng.standard_normal((1, h, s, s)) * 0.3).astype("float32")

    def grads(force_jnp):
        bias = paddle.to_tensor(bias_np.copy(), stop_gradient=False)
        if force_jnp:
            from paddle_tpu.ops import api as _api
            out = _api.sdpa_with_mask(q, k, v, bias, is_causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=bias, is_causal=True)
        (out * out).sum().backward()
        assert bias.grad is not None
        return np.asarray(bias.grad.numpy())

    from paddle_tpu.runtime import device as dev_mod

    saved = dev_mod.is_compiled_with_tpu
    try:
        dev_mod.is_compiled_with_tpu = lambda: True
        with pltpu.force_tpu_interpret_mode():
            g_kernel = grads(force_jnp=False)
    finally:
        dev_mod.is_compiled_with_tpu = saved
    g_ref = grads(force_jnp=True)
    assert np.abs(g_kernel).max() > 0
    np.testing.assert_allclose(g_kernel, g_ref, atol=2e-4, rtol=2e-3)
