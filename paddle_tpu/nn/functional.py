"""paddle.nn.functional surface.

Reference parity: python/paddle/nn/functional/* — re-exports the
tensorized nn ops plus composition helpers.  The fused attention entry
point dispatches to the Pallas flash-attention kernel on TPU
(``FLAGS_use_pallas``) and to the jnp oracle elsewhere.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..common.flags import get_flag
from ..ops.api import (  # noqa: F401
    adaptive_avg_pool2d, adaptive_max_pool2d, avg_pool2d,
    binary_cross_entropy, binary_cross_entropy_with_logits, celu,
    conv1d, conv2d, conv2d_transpose, conv3d, cosine_similarity,
    cross_entropy, dropout, elu, embedding, fused_linear_cross_entropy,
    gelu, glu, group_norm,
    gumbel_softmax, hardshrink, hardsigmoid, hardswish, hardtanh,
    instance_norm, interpolate, kl_div, l1_loss, label_smooth, layer_norm,
    leaky_relu, linear, log_softmax, logsigmoid, max_pool2d, max_pool3d,
    avg_pool3d, maxout, mish,
    mse_loss, nll_loss, normalize, one_hot, pad, pixel_shuffle, prelu,
    relu, relu6, rms_norm, selu, sigmoid, sigmoid_focal_loss, silu,
    smooth_l1_loss, softmax, softplus, softshrink, softsign, swish,
    tanhshrink, thresholded_relu, unfold,
    affine_grid, alpha_dropout, channel_shuffle, dropout2d, dropout3d,
    fold, fused_linear, grid_sample, pixel_unshuffle, upsample,
    square_error_cost, log_loss, hinge_embedding_loss,
    cosine_embedding_loss, margin_ranking_loss, pairwise_distance,
    triplet_margin_loss, triplet_margin_with_distance_loss,
    soft_margin_loss, multi_label_soft_margin_loss, poisson_nll_loss,
    gaussian_nll_loss, ctc_loss, zeropad2d, local_response_norm,
    temporal_shift, rrelu, max_pool1d, avg_pool1d, adaptive_avg_pool1d,
    adaptive_max_pool1d, adaptive_avg_pool3d, adaptive_max_pool3d,
    lp_pool1d, lp_pool2d, max_unpool2d, embedding_bag,
    sequence_mask, dice_loss, npair_loss, multi_margin_loss,
    softmax_with_cross_entropy, feature_alpha_dropout, max_unpool1d,
    max_unpool3d, class_center_sample, margin_cross_entropy,
    adaptive_log_softmax_with_loss, conv1d_transpose, conv3d_transpose,
    bilinear,
)
from ..ops import api as _api
from ..tensor import apply_op
from ..ops.pallas import ShapeNotCovered
from ..runtime import device as _device

batch_norm = _api.batch_norm
scaled_dot_product_attention_ref = _api.scaled_dot_product_attention


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True):
    """Fused attention entry point (paddle F.scaled_dot_product_attention;
    phi fused flash_attn kernel analog).  Layout: [B, S, H, D].

    Routes to the Pallas flash kernel when on TPU with no additive mask and
    no dropout (the fast path used by the LLM recipes); falls back to the
    jnp reference otherwise.
    """
    if attn_mask is None and dropout_p == 0.0:
        # context parallelism: when the active mesh has a sep axis, route
        # through ring/Ulysses attention (SURVEY.md §2.3 sep row)
        from ..distributed.auto_parallel import get_mesh
        pm = get_mesh()
        if pm is not None and pm.mesh.shape.get("sep", 1) > 1:
            from ..distributed.context_parallel import sep_attention_raw
            try:
                return apply_op(sep_attention_raw, query, key, value,
                                causal=is_causal)
            except ShapeNotCovered:
                pass  # shape not sep-shardable; plain paths below
    from ..tensor import Tensor as _T
    # a TRAINED additive bias keeps its REAL gradient via the dmask
    # kernel (round 3); boolean trainable masks make no sense, and a
    # query-broadcast trainable bias is not kernel-covered — those fall
    # back to the jnp path below via ShapeNotCovered
    mask_trainable = (isinstance(attn_mask, _T)
                      and not attn_mask.stop_gradient)
    use_pallas = (
        get_flag("use_pallas")
        and _device.is_compiled_with_tpu()
    )
    if use_pallas:
        from ..ops.pallas.spmd import flash_attention_spmd as kernel
        mask = attn_mask
        if mask is not None:
            if mask_trainable:
                # keep the Tensor so grads flow; a bool mask can't
                # be "trainable" — treat it as constant instead of
                # feeding raw 0/1 to the additive kernel
                if attn_mask.dtype == jnp.bool_:
                    mask_trainable = False
                    mask = jnp.where(attn_mask.value, 0.0,
                                     -1e30).astype(jnp.float32)
                else:
                    mask = attn_mask
            else:
                mval = mask.value if isinstance(mask, _T) \
                    else jnp.asarray(mask)
                # bool masks (True = attend) → additive -inf bias
                if mval.dtype == jnp.bool_:
                    mval = jnp.where(mval, 0.0,
                                     -1e30).astype(jnp.float32)
                mask = mval
        dp = float(dropout_p) if training else 0.0
        try:
            # ShapeNotCovered is the kernel wrappers' documented "shape
            # not covered" signal; anything else (a Mosaic compile
            # error included) is a real bug and must propagate
            if mask_trainable or dp > 0.0:
                import jax as _jax

                from ..ops import random as _R
                from ..ops.pallas.spmd import \
                    flash_attention_spmd_ext
                seed = _jax.random.randint(
                    _R.split_key(), (), 0, 2**31 - 1,
                    dtype=jnp.int32) if dp > 0.0 \
                    else jnp.zeros((), jnp.int32)
                return apply_op(flash_attention_spmd_ext, query, key,
                                value, mask, seed, causal=is_causal,
                                dropout_p=dp,
                                mask_grad=mask_trainable)
            return apply_op(kernel, query, key, value, causal=is_causal,
                            mask=mask)
        except ShapeNotCovered:
            pass
    if mask_trainable:
        # positional-mask variant keeps the trainable bias on the tape
        # (kwargs are static to the op layer)
        return _api.sdpa_with_mask(
            query, key, value, attn_mask, dropout_p=dropout_p,
            is_causal=is_causal, training=training)
    return _api.scaled_dot_product_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    return (out, None) if return_softmax else out


# -- fused step regions (ops/pallas/fused_train) ------------------------------

def add_rms_norm(x, residual, weight, epsilon=1e-6):
    """Fused ``h = residual + x; y = rms_norm(h, weight)``; returns
    ``(h, y)``.  One VMEM pass on TPU, bit-identical jnp composition
    elsewhere — the residual→RMSNorm chain of every pre-norm decoder
    block (RMSNorm.forward_residual routes here)."""
    from ..ops.pallas.fused_train import add_rms_norm_raw
    return apply_op(add_rms_norm_raw, x, residual, weight, epsilon=epsilon)


def add_layer_norm(x, residual, weight, bias, epsilon=1e-5):
    """Fused ``h = residual + x; y = layer_norm(h)`` over the last axis;
    returns ``(h, y)`` (LayerNorm.forward_residual routes here)."""
    from ..ops.pallas.fused_train import add_layer_norm_raw
    return apply_op(add_layer_norm_raw, x, residual, weight, bias,
                    epsilon=epsilon)


def qkv_rope(x, wq, wk, wv, cos, sin, *, n_heads, n_kv, head_dim,
             interleaved=False):
    """The fused rotary→QKV chain: q/k projections with rope applied to
    the matmul output tile in-register, v a plain projection.  Returns
    ``(q, k, v)`` shaped [B, S, heads, head_dim] — bit-identical to the
    unfused project→reshape→rope chain (models/llama.py routes here)."""
    from ..ops.pallas.fused_train import qkv_rope_raw
    return apply_op(qkv_rope_raw, x, wq, wk, wv, cos, sin,
                    n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
                    interleaved=interleaved)
