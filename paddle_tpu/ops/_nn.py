"""Raw neural-network ops.

Reference parity: phi activation/norm/conv/softmax/embedding/loss kernels
(paddle/phi/kernels — incl. gpudnn conv, fusion/fused attention) exposed
with paddle.nn.functional signatures (python/paddle/nn/functional/*).

TPU-native notes: convs lower to XLA ``conv_general_dilated`` (MXU);
attention has a fused Pallas path (ops/pallas/flash_attention.py) selected
by ``FLAGS_use_pallas`` on TPU, with this jnp reference as fallback and
as the numerics oracle in tests.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common.dtype import convert_dtype
from . import random as _random

# -- activations ------------------------------------------------------------


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jax.nn.relu6(x)


def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x, weight, data_format="NCHW"):
    """Per-channel weight broadcasts along the CHANNEL axis (paddle
    contract); scalar weight broadcasts everywhere."""
    w = weight
    if w.ndim == 1 and w.shape[0] > 1 and x.ndim > 1:
        caxis = x.ndim - 1 if data_format.endswith("C") else 1
        shape = [1] * x.ndim
        shape[caxis] = w.shape[0]
        w = w.reshape(shape)
    return jnp.where(x >= 0, x, w * x)


def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=bool(approximate))


def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def hardswish(x):
    return jax.nn.hard_swish(x)


def hardsigmoid(x, slope=1.0 / 6.0, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def tanhshrink(x):
    return x - jnp.tanh(x)


def softplus(x, beta=1.0, threshold=20.0):
    return jax.nn.softplus(beta * x) / beta


def softsign(x):
    return jax.nn.soft_sign(x)


def thresholded_relu(x, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


def maxout(x, groups, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(jnp.reshape(x, new_shape), axis=axis + 1)


def softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(convert_dtype(dtype))
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1, dtype=None):
    if dtype is not None:
        x = x.astype(convert_dtype(dtype))
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    g = _random.gumbel(x.shape).astype(x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis,
                                    inplace=False)
        y = y_hard + lax.stop_gradient(-y) + y  # straight-through
    return y


# -- linear / embedding -----------------------------------------------------

def linear(x, weight, bias=None):
    """paddle F.linear: weight is [in_features, out_features] (NOT torch's
    transposed layout) — x @ W + b."""
    acc = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None
    out = jnp.matmul(x, weight, preferred_element_type=acc)
    if acc is not None:
        out = out.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros_like(out), out)
    return out


# -- normalization ----------------------------------------------------------

def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    axes = tuple(range(x.ndim - len(list(normalized_shape)), x.ndim))
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + epsilon)
    out = out.astype(dt)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, weight=None, epsilon=1e-6, axis=-1):
    """RMSNorm (Llama-family). f32 statistics regardless of input dtype."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
    out = (xf * lax.rsqrt(ms + epsilon)).astype(dt)
    if weight is not None:
        out = out * weight
    return out


def rms_norm_zero_centred(x, weight, epsilon=1e-6):
    """RMSNorm whose stored weight is zero-centred (the scale is ``1 +
    weight``) and whose product is taken in float32 before the cast
    back — the Qwen3-Next family's norm, over the last axis."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + epsilon) \
        * (1.0 + weight.astype(jnp.float32))
    return y.astype(x.dtype)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    dt = x.dtype
    xf = x.astype(jnp.float32).reshape(n, num_groups, c // num_groups, *spatial)
    axes = tuple(range(2, xf.ndim))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = ((xf - mean) * lax.rsqrt(var + epsilon)).reshape(n, c, *spatial)
    out = out.astype(dt)
    if weight is not None:
        out = out * weight.reshape(1, c, *([1] * len(spatial)))
    if bias is not None:
        out = out + bias.reshape(1, c, *([1] * len(spatial)))
    if data_format == "NHWC":
        out = jnp.moveaxis(out, 1, -1)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    c = x.shape[1]
    shape = (1, c) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW"):
    """Returns (out, new_running_mean, new_running_var); the Layer wrapper
    owns the running-stat mutation (functional purity for jit)."""
    caxis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != caxis)
    shape = tuple(x.shape[caxis] if i == caxis else 1 for i in range(x.ndim))
    if training:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    out = (x - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, new_rm, new_rv


def normalize(x, p=2, axis=1, epsilon=1e-12):
    if p == 2:
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    else:
        n = jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(n, epsilon)


# -- dropout ----------------------------------------------------------------

def dropout(x, p=0.5, training=True, mode="upscale_in_train"):
    if not training or p == 0.0:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(_random.split_key(), keep, x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    return jnp.where(mask, x, 0.0).astype(x.dtype)


# -- convolution / pooling --------------------------------------------------

def _norm_tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


def _conv_padding(padding, n, stride, dilation, ksize):
    if isinstance(padding, str):
        return padding.upper()  # SAME / VALID
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * n:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(n)]
    return [tuple(p) for p in padding]


def _match_conv_dtypes(x, weight):
    """amp O2 contract: a low-precision conv weight pulls the input down
    to its dtype (lax.conv requires equal dtypes).  bf16 runs natively
    (the MXU accumulates partial products in f32 internally); float16
    has no safe accumulator on TPU, so fp16 convs run in f32 and cast
    back — same numerics as f32 accumulation, and the autodiff
    transpose stays single-dtype (an explicit preferred_element_type
    trips it on mixed bf16-primal/f32-cotangent operands).

    Returns (x, weight, out_dtype); cast the conv output to out_dtype.
    """
    if x.dtype != weight.dtype:
        x = x.astype(weight.dtype)
    if x.dtype == jnp.float16:
        return x.astype(jnp.float32), weight.astype(jnp.float32), \
            jnp.float16
    return x, weight, None


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    """paddle F.conv2d: weight [C_out, C_in/groups, kH, kW]."""
    x, weight, out_dt = _match_conv_dtypes(x, weight)
    n = 2
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad = _conv_padding(padding, n, stride, dilation, weight.shape[2:])
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "OIHW", "NHWC"))
    # low-precision operands run the conv in their own dtype: the MXU
    # accumulates partial products in f32 internally, and an explicit
    # preferred_element_type here trips mixed-dtype operands in the
    # autodiff transpose (dW-conv of bf16 primal x f32 cotangent)
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if out_dt is not None:
        out = out.astype(out_dt)
    if bias is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    x4 = x[:, :, None, :] if data_format == "NCL" else x[:, None, :, :]
    w4 = weight[:, :, None, :]
    stride = _norm_tuple(stride, 1)
    dilation = _norm_tuple(dilation, 1)
    if isinstance(padding, str):
        pad = padding
    elif isinstance(padding, int):
        pad = [0, padding]
    else:
        pad = [0] + list(padding)
    out = conv2d(x4, w4, bias, (1, stride[0]), pad, (1, dilation[0]), groups,
                 "NCHW")
    return out[:, :, 0, :] if data_format == "NCL" else out[:, 0, :, :]


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    x, weight, out_dt = _match_conv_dtypes(x, weight)
    n = 3
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    pad = _conv_padding(padding, n, stride, dilation, weight.shape[2:])
    dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                    ("NCDHW", "OIDHW", "NCDHW"))
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if out_dt is not None:
        out = out.astype(out_dt)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW"):
    """weight [C_in, C_out/groups, kH, kW] (paddle conv_transpose layout)."""
    x, weight, out_dt = _match_conv_dtypes(x, weight)
    n = 2
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    output_padding = _norm_tuple(output_padding, n)
    if isinstance(padding, str):
        # paddle accepts SAME/VALID here: VALID = no padding; SAME makes
        # out = in*stride (requires effective kernel >= stride)
        k_ = weight.shape[2:]
        if padding.upper() == "VALID":
            padding = 0
        elif padding.upper() == "SAME":
            pads = []
            for i in range(n):
                eff = (k_[i] - 1) * _norm_tuple(dilation, n)[i] + 1
                tot = max(eff - _norm_tuple(stride, n)[i], 0)
                pads.append((tot // 2, tot - tot // 2))
            padding = pads
        else:
            raise ValueError(f"bad conv_transpose padding {padding!r}")
    padv = _norm_tuple(padding, n) if not isinstance(padding, (list, tuple)) \
        or all(isinstance(p, int) for p in padding) else padding
    if isinstance(padv[0], int):
        padv = [(p, p) for p in padv]
    k = weight.shape[2:]
    # transpose-conv as lhs-dilated conv with flipped kernel
    pad_trans = []
    for i in range(n):
        eff_k = (k[i] - 1) * dilation[i] + 1
        lo = eff_k - 1 - padv[i][0]
        hi = eff_k - 1 - padv[i][1] + output_padding[i]
        pad_trans.append((lo, hi))
    w = jnp.flip(weight, axis=(-2, -1))
    # [C_in, C_out/g, kH, kW] -> grouped: out channels = C_out
    cin, cog = weight.shape[0], weight.shape[1]
    w = w.reshape(groups, cin // groups, cog, *k)
    w = jnp.moveaxis(w, 2, 1).reshape(groups * cog, cin // groups, *k)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad_trans,
        lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups)
    if out_dt is not None:
        out = out.astype(out_dt)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCL"):
    """1-D transpose conv through the 2-D path (singleton height)."""
    if data_format == "NLC":
        x = jnp.swapaxes(x, 1, 2)
    s = _norm_tuple(stride, 1)[0]
    p = padding if isinstance(padding, str) else _norm_tuple(padding, 1)[0]
    out = conv2d_transpose(
        x[:, :, None, :], weight[:, :, None, :], bias, (1, s),
        p if isinstance(p, str) else (0, p),
        (0, _norm_tuple(output_padding, 1)[0]),
        (1, _norm_tuple(dilation, 1)[0]), groups)
    out = out[:, :, 0, :]
    return jnp.swapaxes(out, 1, 2) if data_format == "NLC" else out


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW"):
    """weight [C_in, C_out/g, kD, kH, kW]; lhs-dilated conv with a
    flipped kernel, like the 2-D path."""
    if data_format == "NDHWC":
        x = jnp.moveaxis(x, -1, 1)
    x, weight, out_dt = _match_conv_dtypes(x, weight)
    n = 3
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    output_padding = _norm_tuple(output_padding, n)
    padv = _norm_tuple(padding, n)
    padv = [(p, p) for p in padv]
    k = weight.shape[2:]
    pad_trans = []
    for i in range(n):
        eff_k = (k[i] - 1) * dilation[i] + 1
        lo = eff_k - 1 - padv[i][0]
        hi = eff_k - 1 - padv[i][1] + output_padding[i]
        pad_trans.append((lo, hi))
    w = jnp.flip(weight, axis=(-3, -2, -1))
    cin, cog = weight.shape[0], weight.shape[1]
    w = w.reshape(groups, cin // groups, cog, *k)
    w = jnp.moveaxis(w, 2, 1).reshape(groups * cog, cin // groups, *k)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCDHW", "OIDHW", "NCDHW"))
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1, 1), padding=pad_trans,
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)
    if out_dt is not None:
        out = out.astype(out_dt)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return jnp.moveaxis(out, 1, -1) if data_format == "NDHWC" else out


def bilinear(x1, x2, weight, bias=None):
    """paddle F.bilinear: out[n, o] = x1[n] @ W[o] @ x2[n] (+ b)."""
    out = jnp.einsum("ni,oij,nj->no", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def _channel_last_aware(fn):
    """Pool-family decorator: a channel-last ``data_format`` kwarg
    ("NHWC"/"NDHWC") transposes to channel-first, runs the NC*-native
    body, and transposes every output back (mask values are plane-flat
    spatial indices, layout-independent)."""
    import functools as _ft

    @_ft.wraps(fn)
    def wrapped(x, *args, **kwargs):
        df = kwargs.get("data_format")
        if df and len(df) > 2 and df.endswith("C"):
            perm = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
            inv = (0,) + tuple(range(2, x.ndim)) + (1,)
            kwargs["data_format"] = df[0] + "C" + df[1:-1]
            out = fn(jnp.transpose(x, perm), *args, **kwargs)
            if isinstance(out, tuple):
                return tuple(jnp.transpose(o, inv) for o in out)
            return jnp.transpose(out, inv)
        return fn(x, *args, **kwargs)
    return wrapped


def _ceil_mode_pads(spatial, k, s, p):
    """Extend the high-side pads so reduce_window emits ceil-divided
    output sizes.  The extra window must start inside input + left pad
    (torch/paddle rule); max pools pad with -inf so the extension never
    changes window maxima."""
    out = []
    for d, dim in enumerate(spatial):
        lo, hi = p[d]
        eff = dim + lo + hi
        n_floor = (eff - k[d]) // s[d] + 1
        n_ceil = -(-(eff - k[d]) // s[d]) + 1
        if n_ceil > n_floor and (n_ceil - 1) * s[d] >= dim + lo:
            n_ceil -= 1
        extra = (n_ceil - 1) * s[d] + k[d] - eff
        out.append((lo, hi + max(extra, 0)))
    return out


@_channel_last_aware
def max_pool2d(x, kernel_size, stride=None, padding=0,
               return_mask=False, ceil_mode=False, data_format="NCHW"):
    # paddle argument ORDER kept exactly (return_mask BEFORE ceil_mode)
    # — positional paddle code like max_pool2d(x, 2, 2, 0, True) must
    # mean return_mask=True here too
    n = 2
    k = _norm_tuple(kernel_size, n)
    s = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _conv_padding(padding, n, s, (1, 1), k)
    if ceil_mode and not isinstance(p, str):
        p = _ceil_mode_pads(x.shape[2:2 + n], k, s, p)
    if return_mask:
        if ceil_mode and (x.shape[2] % k[0] or x.shape[3] % k[1]):
            raise NotImplementedError(
                "max_pool2d(return_mask=True, ceil_mode=True) with a "
                "partial trailing window is not supported")
        # mask = flat argmax position within each (N, C) plane (the
        # max_unpool2d contract).  Non-overlapping unpadded windows —
        # the SegNet pool/unpool pairing — are exact via the window
        # reshape; other geometries (overlap, any padding incl.
        # "SAME") are not supported.
        if (list(s) != list(k) or isinstance(p, str)
                or any(a or b for a, b in p)):
            raise NotImplementedError(
                "max_pool2d(return_mask=True) supports stride == "
                "kernel_size with no padding")
        nb, c, h, w = x.shape
        oh, ow = h // k[0], w // k[1]
        win = x[:, :, :oh * k[0], :ow * k[1]].reshape(
            nb, c, oh, k[0], ow, k[1])
        win = jnp.moveaxis(win, 3, 4).reshape(nb, c, oh, ow,
                                              k[0] * k[1])
        # out derived from the SAME window tensor: out/mask shape
        # agreement holds by construction, no second reduction
        out = jnp.max(win, axis=-1)
        flat_in_win = jnp.argmax(win, axis=-1)
        wr = flat_in_win // k[1]
        wc = flat_in_win % k[1]
        rows = jnp.arange(oh)[None, None, :, None] * k[0] + wr
        cols = jnp.arange(ow)[None, None, None, :] * k[1] + wc
        mask = (rows * w + cols).astype(jnp.int32)
        return out, mask
    pads = p if isinstance(p, str) else [(0, 0), (0, 0)] + list(p)
    dims = (1, 1) + k
    strides = (1, 1) + s
    out = lax.reduce_window(x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                            else jnp.iinfo(x.dtype).min,
                            lax.max, dims, strides, pads)
    return out


@_channel_last_aware
def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW"):
    n = 3
    k = _norm_tuple(kernel_size, n)
    s = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _conv_padding(padding, n, s, (1, 1, 1), k)
    if return_mask:
        # same contract as max_pool2d: non-overlapping unpadded windows
        # only (the pool/unpool pairing); mask = flat DHW argmax index
        if (list(s) != list(k) or isinstance(p, str)
                or any(a or b for a, b in p)):
            raise NotImplementedError(
                "max_pool3d(return_mask=True) supports stride == "
                "kernel_size with no padding")
        if ceil_mode and any(x.shape[2 + i] % k[i] for i in range(3)):
            raise NotImplementedError(
                "max_pool3d(return_mask=True, ceil_mode=True) with a "
                "partial trailing window is not supported")
        nb, c, d, h, w = x.shape
        od, oh, ow = d // k[0], h // k[1], w // k[2]
        win = x[:, :, :od * k[0], :oh * k[1], :ow * k[2]].reshape(
            nb, c, od, k[0], oh, k[1], ow, k[2])
        win = jnp.transpose(win, (0, 1, 2, 4, 6, 3, 5, 7)).reshape(
            nb, c, od, oh, ow, k[0] * k[1] * k[2])
        out = jnp.max(win, axis=-1)
        flat = jnp.argmax(win, axis=-1)
        wd = flat // (k[1] * k[2])
        wh = (flat // k[2]) % k[1]
        ww = flat % k[2]
        ds = jnp.arange(od)[None, None, :, None, None] * k[0] + wd
        hs = jnp.arange(oh)[None, None, None, :, None] * k[1] + wh
        ws = jnp.arange(ow)[None, None, None, None, :] * k[2] + ww
        mask = ((ds * h + hs) * w + ws).astype(jnp.int32)
        return out, mask
    if ceil_mode and not isinstance(p, str):
        p = _ceil_mode_pads(x.shape[2:2 + n], k, s, p)
    pads = p if isinstance(p, str) else [(0, 0), (0, 0)] + list(p)
    out = lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else jnp.iinfo(x.dtype).min,
        lax.max, (1, 1) + k, (1, 1) + s, pads)
    return out


@_channel_last_aware
def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None,
               data_format="NCDHW"):
    n = 3
    k = _norm_tuple(kernel_size, n)
    s = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _conv_padding(padding, n, s, (1, 1, 1), k)
    pads = p if isinstance(p, str) else [(0, 0), (0, 0)] + list(p)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, 1) + k, (1, 1) + s,
                               pads)
    if divisor_override:
        return summed / divisor_override
    if exclusive and not isinstance(pads, str):
        counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add,
                                   (1, 1) + k, (1, 1) + s, pads)
        return summed / counts
    return summed / float(np.prod(k))


@_channel_last_aware
def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW"):
    n = 2
    k = _norm_tuple(kernel_size, n)
    s = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _conv_padding(padding, n, s, (1, 1), k)
    pads = p if isinstance(p, str) else [(0, 0), (0, 0)] + list(p)
    dims = (1, 1) + k
    strides = (1, 1) + s
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    if divisor_override:
        return summed / divisor_override
    if exclusive and not isinstance(pads, str):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
        return summed / counts
    return summed / float(np.prod(k))


def _adaptive_pool2d(x, output_size, reduce_fn):
    """General adaptive pooling: bin i covers [floor(i*H/out),
    ceil((i+1)*H/out)) — small static python loops over output bins
    (output sizes are tiny; XLA fuses the slices)."""
    oh, ow = _norm_tuple(output_size, 2)
    h, w = x.shape[2], x.shape[3]
    rows = []
    for i in range(oh):
        h0, h1 = (i * h) // oh, -(-((i + 1) * h) // oh)
        cols = []
        for j in range(ow):
            w0, w1 = (j * w) // ow, -(-((j + 1) * w) // ow)
            cols.append(reduce_fn(x[:, :, h0:h1, w0:w1]))
        rows.append(jnp.stack(cols, -1))
    return jnp.stack(rows, -2)


@_channel_last_aware
def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    h, w = x.shape[2], x.shape[3]
    if h % out[0] == 0 and w % out[1] == 0:
        kh, kw = h // out[0], w // out[1]
        return avg_pool2d(x, (kh, kw), (kh, kw), 0)
    return _adaptive_pool2d(x, out, lambda s: jnp.mean(s, axis=(2, 3)))


@_channel_last_aware
def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    out = _norm_tuple(output_size, 2)
    h, w = x.shape[2], x.shape[3]
    if h % out[0] == 0 and w % out[1] == 0:
        kh, kw = h // out[0], w // out[1]
        return max_pool2d(x, (kh, kw), (kh, kw), 0)
    return _adaptive_pool2d(x, out, lambda s: jnp.max(s, axis=(2, 3)))


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    n, c, h, w = x.shape
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    p = _norm_tuple(paddings, 2)
    x = jnp.pad(x, [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s, padding="VALID",
        rhs_dilation=d, dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return patches.reshape(n, c * k[0] * k[1], -1)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    if data_format != "NCHW":
        raise NotImplementedError
    n, c, h, w = x.shape
    if size is None:
        sf = _norm_tuple(scale_factor, 2) if not isinstance(scale_factor, (int, float)) \
            else (scale_factor, scale_factor)
        size = (int(h * sf[0]), int(w * sf[1]))
    size = _norm_tuple(size, 2)
    method = {"nearest": "nearest", "bilinear": "bilinear", "linear": "bilinear",
              "bicubic": "bicubic", "area": "linear"}[mode]
    xt = jnp.moveaxis(x, 1, -1)
    out = jax.image.resize(xt, (n, size[0], size[1], c), method=method)
    return jnp.moveaxis(out, -1, 1).astype(x.dtype)


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


# -- attention --------------------------------------------------------------

def sdpa_with_mask(query, key, value, attn_mask, dropout_p=0.0,
                   is_causal=False, training=True, scale=None):
    """scaled_dot_product_attention with the mask as a POSITIONAL tensor
    input: keyword args are static to the op layer, so a trainable
    additive bias passed as ``attn_mask=`` would silently lose its
    gradient — this entry keeps it on the tape."""
    return scaled_dot_product_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training, scale=scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None):
    """Reference (jnp) attention: q/k/v are [B, S, H, D] (paddle layout).

    The fused Pallas flash-attention path (ops/pallas) supersedes this on
    TPU; this is the numerics oracle and CPU fallback.
    """
    b, sq, h, d = query.shape
    sk = key.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    q = jnp.moveaxis(query, 2, 1)  # B H S D
    k = jnp.moveaxis(key, 2, 1)
    v = jnp.moveaxis(value, 2, 1)
    if k.shape[1] != h:  # GQA: repeat kv heads
        rep = h // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(query.dtype)
    if dropout_p > 0.0 and training:
        probs = dropout(probs, dropout_p, training=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.moveaxis(out, 1, 2)  # back to B S H D


# -- losses -----------------------------------------------------------------

def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    """paddle F.cross_entropy: input = logits (use_softmax=True default)."""
    if use_softmax:
        logp = jax.nn.log_softmax(input.astype(jnp.float32), axis=axis)
    else:
        logp = jnp.log(jnp.clip(input.astype(jnp.float32), 1e-30, None))
    nclass = input.shape[axis]
    if soft_label:
        lbl = label.astype(jnp.float32)
        loss = -jnp.sum(lbl * logp, axis=axis)
        valid = None
    else:
        lbl = label
        if lbl.ndim == logp.ndim:
            lbl = jnp.squeeze(lbl, axis=axis)
        if label_smoothing > 0.0:
            onehot = jax.nn.one_hot(lbl, nclass, axis=axis)
            smoothed = onehot * (1 - label_smoothing) + label_smoothing / nclass
            loss = -jnp.sum(smoothed * logp, axis=axis)
        else:
            lbl_safe = jnp.where(lbl == ignore_index, 0, lbl)
            loss = -jnp.take_along_axis(
                logp, jnp.expand_dims(lbl_safe, axis), axis=axis
            ).squeeze(axis)
        valid = (lbl != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
        if weight is not None:
            w = jnp.take(weight, jnp.where(lbl == ignore_index, 0, lbl))
            w = jnp.where(valid, w, 0.0)
            loss = loss * w
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
        if reduction == "mean" and valid is not None:
            denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            return jnp.sum(loss) / denom
    return _reduce(loss, reduction)


def fused_linear_cross_entropy(x, weight, label, bias=None,
                               ignore_index=-100, reduction="mean",
                               transpose_weight=False, chunk_size=1024):
    """Fused LM-head matmul + softmax cross-entropy, chunked over tokens.

    Reference parity: phi fused kernels (fused_softmax_mask /
    parallel cross-entropy-with-logits, SURVEY.md §2.1) — the paddle
    recipe computes full [N, V] logits then CE; at V=32k-128k the fp32
    logits and their gradient dominate HBM.  TPU-native design: scan
    over token chunks, computing each chunk's logits inside a
    ``jax.checkpoint`` region so they are recomputed (not stored) in
    backward — peak memory drops from O(N·V) to O(chunk·V) while the
    matmuls stay MXU-sized.

    x: [..., H]; weight: [H, V] (paddle Linear layout) or [V, H] with
    ``transpose_weight=True`` (tied-embedding layout); label: [...].
    """
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    lab = label.reshape(-1)
    n = x2.shape[0]
    c = min(chunk_size, n)
    pad = (-n) % c
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, h), x2.dtype)], axis=0)
        lab = jnp.concatenate(
            [lab, jnp.full((pad,), ignore_index, lab.dtype)], axis=0)
    n_chunks = (n + pad) // c
    xc_all = x2.reshape(n_chunks, c, h)
    lab_all = lab.reshape(n_chunks, c)

    @jax.checkpoint
    def chunk_loss(xc, lc):
        logits = jnp.dot(xc, weight.T if transpose_weight else weight,
                         preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.where(lc == ignore_index, 0, lc)
        tgt = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        valid = lc != ignore_index
        per_tok = jnp.where(valid, lse - tgt, 0.0)
        return per_tok, valid.astype(jnp.float32)

    # accumulate via stacked scan OUTPUTS (empty carry): a carry would
    # need its varying-manual-axes type to match the body's, which breaks
    # when this runs inside a shard_map region (the pipeline loss tail)
    def body(carry, inp):
        per_tok, valid = chunk_loss(*inp)
        if reduction == "none":
            return carry, per_tok
        return carry, (jnp.sum(per_tok), jnp.sum(valid))

    _, ys = jax.lax.scan(body, (), (xc_all, lab_all))
    if reduction == "none":
        return ys.reshape(-1)[:n].reshape(label.shape)
    total, count = jnp.sum(ys[0]), jnp.sum(ys[1])
    if reduction == "sum":
        return total
    return total / jnp.maximum(count, 1.0)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    loss = -jnp.take_along_axis(input, label[..., None], axis=-1)[..., 0]
    valid = label != ignore_index
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)
    return _reduce(loss, reduction)


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    x = jnp.clip(input.astype(jnp.float32), 1e-12, 1 - 1e-12)
    loss = -(label * jnp.log(x) + (1 - label) * jnp.log1p(-x))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    x = logit.astype(jnp.float32)
    lbl = label.astype(jnp.float32)
    mx = jnp.clip(x, 0, None)
    loss = mx - x * lbl + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if pos_weight is not None:
        log_weight = (pos_weight - 1) * lbl + 1
        loss = loss * log_weight  # approximation consistent at extremes
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def mse_loss(input, label, reduction="mean"):
    return _reduce(jnp.square(input - label), reduction)


def l1_loss(input, label, reduction="mean"):
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = input - label
    loss = jnp.where(jnp.abs(d) < delta, 0.5 * d * d / delta,
                     jnp.abs(d) - 0.5 * delta)
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        loss = label * (jnp.log(jnp.clip(label, 1e-30, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(x1 * x1, axis=axis))
    n2 = jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = binary_cross_entropy_with_logits(logit, label, reduction="none")
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * ((1 - p_t) ** gamma)
    if alpha >= 0:
        a_t = alpha * label + (1 - alpha) * (1 - label)
        loss = a_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is None:
        return (1 - epsilon) * label + epsilon / n
    return (1 - epsilon) * label + epsilon * prior_dist


def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // r, r, w // r, r)
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4)).reshape(
        n, c * r * r, h // r, w // r)
    if data_format == "NHWC":
        x = jnp.moveaxis(x, 1, -1)
    return x


def channel_shuffle(x, groups, data_format="NCHW"):
    if data_format == "NHWC":
        x = jnp.moveaxis(x, -1, 1)
    n, c, h, w = x.shape
    x = x.reshape(n, groups, c // groups, h, w)
    x = jnp.swapaxes(x, 1, 2).reshape(n, c, h, w)
    if data_format == "NHWC":
        x = jnp.moveaxis(x, 1, -1)
    return x


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    """Whole-channel dropout (paddle F.dropout2d)."""
    if not training or p == 0.0:
        return x
    caxis = 1 if data_format == "NCHW" else 3
    shape = tuple(x.shape[i] if i in (0, caxis) else 1
                  for i in range(x.ndim))
    keep = jax.random.bernoulli(_random.split_key(), 1.0 - p,
                                shape).astype(x.dtype)
    return x * keep / (1.0 - p)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    if not training or p == 0.0:
        return x
    caxis = 1 if data_format == "NCDHW" else 4
    shape = tuple(x.shape[i] if i in (0, caxis) else 1
                  for i in range(x.ndim))
    keep = jax.random.bernoulli(_random.split_key(), 1.0 - p,
                                shape).astype(x.dtype)
    return x * keep / (1.0 - p)


def alpha_dropout(x, p=0.5, training=True):
    """SELU-preserving dropout (paddle F.alpha_dropout)."""
    if not training or p == 0.0:
        return x
    alpha_p = -1.7580993408473766
    keep = jax.random.bernoulli(_random.split_key(), 1.0 - p, x.shape)
    a = ((1.0 - p) * (1.0 + p * alpha_p ** 2)) ** -0.5
    b = -a * p * alpha_p
    return a * jnp.where(keep, x, alpha_p) + b


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0,
         dilations=1):
    """Inverse of unfold: [N, C*kh*kw, L] -> [N, C, H, W] with
    overlapping patches summed (col2im)."""
    n, ckk, L = x.shape
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    p = _norm_tuple(paddings, 2)
    out_h, out_w = _norm_tuple(output_sizes, 2)
    c = ckk // (k[0] * k[1])
    ph, pw = out_h + 2 * p[0], out_w + 2 * p[1]
    nh = (ph - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
    nw = (pw - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
    cols = x.reshape(n, c, k[0], k[1], nh, nw)
    out = jnp.zeros((n, c, ph, pw), x.dtype)
    for i in range(k[0]):
        for j in range(k[1]):
            hs = i * d[0]
            ws = j * d[1]
            out = out.at[:, :, hs:hs + nh * s[0]:s[0],
                         ws:ws + nw * s[1]:s[1]].add(cols[:, :, i, j])
    return out[:, :, p[0]:p[0] + out_h, p[1]:p[1] + out_w]


def affine_grid(theta, out_shape, align_corners=True):
    """paddle F.affine_grid: theta [N, 2, 3] -> grid [N, H, W, 2]."""
    n, _, h, w = [int(v) for v in out_shape]
    if align_corners:
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
    else:
        ys = (jnp.arange(h) + 0.5) * 2.0 / h - 1.0
        xs = (jnp.arange(w) + 0.5) * 2.0 / w - 1.0
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    base = jnp.stack([gx, gy, jnp.ones_like(gx)], -1)      # [H, W, 3]
    return jnp.einsum("nij,hwj->nhwi", jnp.asarray(theta), base)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """paddle F.grid_sample (NCHW, bilinear/nearest, zeros/border)."""
    n, c, h, w = x.shape
    gx, gy = grid[..., 0], grid[..., 1]
    if align_corners:
        fx = (gx + 1.0) * (w - 1) / 2.0
        fy = (gy + 1.0) * (h - 1) / 2.0
    else:
        fx = ((gx + 1.0) * w - 1.0) / 2.0
        fy = ((gy + 1.0) * h - 1.0) / 2.0

    def gather(iy, ix):
        iyc = jnp.clip(iy, 0, h - 1)
        ixc = jnp.clip(ix, 0, w - 1)
        vals = x[jnp.arange(n)[:, None, None], :, iyc, ixc]  # [N,Hg,Wg,C]
        if padding_mode == "zeros":
            ok = ((iy >= 0) & (iy <= h - 1) & (ix >= 0) &
                  (ix <= w - 1))[..., None]
            vals = jnp.where(ok, vals, 0.0)
        return vals

    if mode == "nearest":
        out = gather(jnp.round(fy).astype(jnp.int32),
                     jnp.round(fx).astype(jnp.int32))
    else:
        x0 = jnp.floor(fx).astype(jnp.int32)
        y0 = jnp.floor(fy).astype(jnp.int32)
        x1, y1 = x0 + 1, y0 + 1
        wx = fx - x0
        wy = fy - y0
        out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[..., None] +
               gather(y0, x1) * (wx * (1 - wy))[..., None] +
               gather(y1, x0) * ((1 - wx) * wy)[..., None] +
               gather(y1, x1) * (wx * wy)[..., None])
    return jnp.moveaxis(out, -1, 1)                          # NCHW


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, data_format="NCHW"):
    return interpolate(x, size=size, scale_factor=scale_factor, mode=mode,
                       align_corners=align_corners,
                       data_format=data_format)


def fused_linear(x, weight, bias=None, transpose_weight=False):
    w = weight.T if transpose_weight else weight
    return linear(x, w, bias)


# -- round-4 long-tail batch: losses / pools / misc (VERDICT r3 #3) ---------

def square_error_cost(input, label):
    return jnp.square(input - label)


def log_loss(input, label, epsilon=1e-4):
    return (-label * jnp.log(input + epsilon)
            - (1.0 - label) * jnp.log(1.0 - input + epsilon))


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1.0, input,
                     jnp.maximum(0.0, margin - input))
    return _reduce(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean"):
    cos = jnp.sum(input1 * input2, axis=-1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1)
        * jnp.linalg.norm(input2, axis=-1), 1e-12)
    loss = jnp.where(label == 1.0, 1.0 - cos,
                     jnp.maximum(0.0, cos - margin))
    return _reduce(loss, reduction)


def margin_ranking_loss(input, other, label, margin=0.0,
                        reduction="mean"):
    return _reduce(jnp.maximum(0.0, -label * (input - other) + margin),
                   reduction)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    d = jnp.linalg.norm(x - y + epsilon, ord=p, axis=-1,
                        keepdims=keepdim)
    return d


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    dp = pairwise_distance(input, positive, p, epsilon)
    dn = pairwise_distance(input, negative, p, epsilon)
    if swap:
        dn = jnp.minimum(dn, pairwise_distance(positive, negative, p,
                                               epsilon))
    return _reduce(jnp.maximum(0.0, dp - dn + margin), reduction)


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean"):
    dist = distance_function or (
        lambda a, b: jnp.linalg.norm(a - b, axis=-1))
    dp = dist(input, positive)
    dn = dist(input, negative)
    if swap:
        dn = jnp.minimum(dn, dist(positive, negative))
    return _reduce(jnp.maximum(0.0, dp - dn + margin), reduction)


def soft_margin_loss(input, label, reduction="mean"):
    return _reduce(jnp.log1p(jnp.exp(-label * input)), reduction)


def multi_label_soft_margin_loss(input, label, weight=None,
                                 reduction="mean"):
    loss = -(label * jax.nn.log_sigmoid(input)
             + (1.0 - label) * jax.nn.log_sigmoid(-input))
    if weight is not None:
        loss = loss * weight
    return _reduce(jnp.mean(loss, axis=-1), reduction)


def poisson_nll_loss(input, label, log_input=True, full=False,
                     epsilon=1e-8, reduction="mean"):
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        stirling = (label * jnp.log(label + epsilon) - label
                    + 0.5 * jnp.log(2.0 * np.pi * (label + epsilon)))
        loss = loss + jnp.where(label > 1, stirling, 0.0)
    return _reduce(loss, reduction)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean"):
    var = jnp.maximum(variance, epsilon)
    loss = 0.5 * (jnp.log(var) + jnp.square(input - label) / var)
    if full:
        loss = loss + 0.5 * jnp.log(jnp.asarray(2.0 * np.pi))
    return _reduce(loss, reduction)


def ctc_loss(log_probs, labels, input_lengths, label_lengths,
             blank=0, reduction="mean", norm_by_times=False):
    """CTC loss via the standard log-semiring forward DP, scanned over
    time (paddle: log_probs [T, B, C] logits, labels [B, L] int).
    Returns per-sequence negative log likelihood, reduced."""
    t_max, b, _ = log_probs.shape
    lp = jax.nn.log_softmax(log_probs.astype(jnp.float32), axis=-1)
    l_max = labels.shape[1]
    s = 2 * l_max + 1
    # extended label sequence: blank, l1, blank, l2, ..., blank
    ext = jnp.full((b, s), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    neg = jnp.float32(-1e30)
    # alpha init: positions 0 (blank) and 1 (first label)
    a0 = jnp.full((b, s), neg)
    a0 = a0.at[:, 0].set(lp[0, jnp.arange(b), ext[:, 0]])
    a0 = a0.at[:, 1].set(jnp.where(
        label_lengths > 0, lp[0, jnp.arange(b), ext[:, 1]], neg))

    same = jnp.concatenate(
        [jnp.ones((b, 2), bool),
         ext[:, 2:] == ext[:, :-2]], axis=1)      # skip-path blocked

    def step(alpha, lp_t):
        prev1 = jnp.concatenate([jnp.full((b, 1), neg),
                                 alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((b, 2), neg),
                                 alpha[:, :-2]], axis=1)
        prev2 = jnp.where(same, neg, prev2)
        merged = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        return merged + emit, merged

    ts = jnp.arange(1, t_max)

    def scan_body(carry, ti):
        alpha = carry
        new, _ = step(alpha, lp[ti])
        # sequences shorter than t keep their final alpha
        keep = (ti < input_lengths)[:, None]
        return jnp.where(keep, new, alpha), None

    alpha, _ = jax.lax.scan(scan_body, a0, ts)
    # NLL = -logaddexp(alpha[L*2], alpha[L*2-1]) at t = len-1
    idx_last = 2 * label_lengths.astype(jnp.int32)
    bidx = jnp.arange(b)
    end1 = alpha[bidx, idx_last]
    end2 = jnp.where(label_lengths > 0,
                     alpha[bidx, jnp.maximum(idx_last - 1, 0)], neg)
    nll = -jnp.logaddexp(end1, end2)
    if norm_by_times:
        nll = nll / jnp.maximum(input_lengths.astype(jnp.float32), 1.0)
    if reduction == "mean":
        # paddle divides each sequence's NLL by its label length first
        return jnp.mean(
            nll / jnp.maximum(label_lengths.astype(jnp.float32), 1.0))
    return _reduce(nll, reduction)


def zeropad2d(x, padding, data_format="NCHW"):
    l, r, t_, b_ = _norm_tuple(padding, 4)
    return jnp.pad(x, [(0, 0), (0, 0), (t_, b_), (l, r)])


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW"):
    # paddle implements square -> pad -> AVG_pool -> scale, so the alpha
    # term is alpha * sum(x^2) / size, not alpha * sum(x^2)
    sq = jnp.square(x)
    half = size // 2
    pad = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2)
    padded = jnp.pad(sq, pad)
    acc = sum(padded[:, i:i + x.shape[1]] for i in range(size)) / size
    return x / jnp.power(k + alpha * acc, beta)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW"):
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    back = jnp.concatenate([xr[:, 1:, :fold],
                            jnp.zeros_like(xr[:, :1, :fold])], axis=1)
    fwd = jnp.concatenate([jnp.zeros_like(xr[:, :1, fold:2 * fold]),
                           xr[:, :-1, fold:2 * fold]], axis=1)
    rest = xr[:, :, 2 * fold:]
    return jnp.concatenate([back, fwd, rest],
                           axis=2).reshape(nt, c, h, w)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False):
    if training:
        # per-element slope from the library's seeded keyed RNG (a
        # host-side scalar would bake one constant slope under jit)
        a = jax.random.uniform(_random.split_key(), x.shape,
                               minval=lower, maxval=upper)
    else:
        a = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, a * x)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False):
    x4 = x[:, :, None, :]
    k = _norm_tuple(kernel_size, 1)[0]
    s = _norm_tuple(stride if stride is not None else kernel_size, 1)[0]
    p = _norm_tuple(padding, 1)[0]
    if return_mask:
        out, mask = max_pool2d(x4, (1, k), (1, s), (0, p),
                               return_mask=True, ceil_mode=ceil_mode)
        # plane width == L, single row: the 2D flat index IS the 1D one
        return out[:, :, 0, :], mask[:, :, 0, :]
    return max_pool2d(x4, (1, k), (1, s), (0, p),
                      ceil_mode=ceil_mode)[:, :, 0, :]


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False):
    x4 = x[:, :, None, :]
    k = _norm_tuple(kernel_size, 1)[0]
    s = _norm_tuple(stride if stride is not None else kernel_size, 1)[0]
    p = _norm_tuple(padding, 1)[0]
    return avg_pool2d(x4, (1, k), (1, s), (0, p),
                      exclusive=exclusive)[:, :, 0, :]


def adaptive_avg_pool1d(x, output_size):
    x4 = x[:, :, None, :]
    return adaptive_avg_pool2d(x4, (1, output_size))[:, :, 0, :]


def adaptive_max_pool1d(x, output_size, return_mask=False):
    if return_mask:
        o = _norm_tuple(output_size, 1)[0]
        length = x.shape[-1]
        outs, idxs = [], []
        for i in range(o):
            l0, l1 = (i * length) // o, -(-((i + 1) * length) // o)
            seg = x[:, :, l0:l1]
            outs.append(jnp.max(seg, axis=-1))
            idxs.append(jnp.argmax(seg, axis=-1) + l0)
        return (jnp.stack(outs, -1),
                jnp.stack(idxs, -1).astype(jnp.int32))
    x4 = x[:, :, None, :]
    return adaptive_max_pool2d(x4, (1, output_size))[:, :, 0, :]


def _adaptive_pool3d(x, output_size, reduce_fn):
    od, oh, ow = _norm_tuple(output_size, 3)
    d = x.shape[2]
    outs = []
    for i in range(od):
        d0, d1 = (i * d) // od, -(-((i + 1) * d) // od)
        plane = reduce_fn(x[:, :, d0:d1], axis=2)
        outs.append(plane)
    planes = jnp.stack(outs, axis=2)   # [N, C, od, H, W]
    n, c, od_, h, w = planes.shape
    flat = planes.reshape(n, c * od_, h, w)
    pooled = _adaptive_pool2d(flat, (oh, ow),
                              lambda s: reduce_fn(s, axis=(2, 3)))
    return pooled.reshape(n, c, od_, oh, ow)


def adaptive_avg_pool3d(x, output_size):
    return _adaptive_pool3d(x, output_size, jnp.mean)


def adaptive_max_pool3d(x, output_size, return_mask=False):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool3d(return_mask=True) is not supported "
            "(same stance as max_pool3d)")
    return _adaptive_pool3d(x, output_size, jnp.max)


def lp_pool1d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False):
    p = float(norm_type)
    k = _norm_tuple(kernel_size, 1)[0]
    s = _norm_tuple(stride if stride is not None else kernel_size, 1)[0]
    summed = avg_pool1d(jnp.power(jnp.abs(x), p), k, s, padding,
                        exclusive=False) * k
    return jnp.power(summed, 1.0 / p)


def lp_pool2d(x, norm_type, kernel_size, stride=None, padding=0,
              ceil_mode=False, data_format="NCHW"):
    p = float(norm_type)
    k = _norm_tuple(kernel_size, 2)
    summed = avg_pool2d(jnp.power(jnp.abs(x), p), k, stride, padding,
                        exclusive=False) * float(np.prod(k))
    return jnp.power(summed, 1.0 / p)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None):
    """Scatter pooled values back to their argmax positions.  indices:
    flat positions within each (N, C) plane (paddle's convention)."""
    k = _norm_tuple(kernel_size, 2)
    s = _norm_tuple(stride if stride is not None else kernel_size, 2)
    n, c, h, w = x.shape
    if output_size is None:
        oh = (h - 1) * s[0] + k[0] - 2 * _norm_tuple(padding, 2)[0]
        ow = (w - 1) * s[1] + k[1] - 2 * _norm_tuple(padding, 2)[1]
    else:
        oh, ow = output_size[-2], output_size[-1]
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    idx = indices.reshape(n, c, h * w).astype(jnp.int32)
    flat = flat.at[
        jnp.arange(n)[:, None, None], jnp.arange(c)[None, :, None],
        idx].set(x.reshape(n, c, h * w))
    return flat.reshape(n, c, oh, ow)


def embedding_bag(input, weight, offsets=None, mode="mean"):
    """Gather + segment-reduce (paddle/torch embedding_bag, 2D input
    form: input [B, L] -> [B, D] reduced embeddings).  The ragged
    1D+offsets form is not supported — reject it rather than reduce
    over the wrong axis."""
    if offsets is not None or input.ndim != 2:
        raise NotImplementedError(
            "embedding_bag supports the 2D input form only "
            "(input [B, L], offsets=None)")
    emb = weight[input]                       # [B, L, D]
    if mode == "sum":
        return jnp.sum(emb, axis=1)
    if mode == "max":
        return jnp.max(emb, axis=1)
    return jnp.mean(emb, axis=1)


# -- round-5 long-tail batch (VERDICT r4 #10) --------------------------------

def sequence_mask(x, maxlen=None, dtype="int64"):
    """paddle.nn.functional.sequence_mask: [..., maxlen] with 1 where
    position < length."""
    import numpy as _np
    if maxlen is None:
        maxlen = int(_np.asarray(jax.device_get(x)).max())
    pos = jnp.arange(maxlen)
    return (pos < x[..., None]).astype(dtype)


def dice_loss(input, label, epsilon=1e-5):
    """Dice loss over the last (class-prob) axis; label holds class ids
    [..., 1] (paddle F.dice_loss contract)."""
    nclass = input.shape[-1]
    oh = jax.nn.one_hot(label.squeeze(-1), nclass, dtype=input.dtype)
    reduce_axes = tuple(range(1, input.ndim))
    inter = jnp.sum(input * oh, axis=reduce_axes)
    union = jnp.sum(input, axis=reduce_axes) + jnp.sum(oh, axis=reduce_axes)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """N-pair loss (Sohn 2016): softmax CE over anchor@positive.T with
    same-label targets, + L2 on the embeddings."""
    labels = labels.reshape(-1)
    same = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    tgt = same / jnp.sum(same, axis=1, keepdims=True)
    sim = anchor @ positive.T
    xent = jnp.mean(jnp.sum(
        tgt * (jax.nn.logsumexp(sim, axis=1, keepdims=True) - sim), axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(anchor * anchor, axis=1))
                    + jnp.mean(jnp.sum(positive * positive,
                                       axis=1))) * 0.25
    return xent + reg


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean"):
    """paddle F.multi_margin_loss: hinge loss against every wrong class."""
    n, c = input.shape
    tgt = jnp.take_along_axis(input, label[:, None].astype(jnp.int32), 1)
    m = jnp.maximum(0.0, margin - tgt + input) ** p
    if weight is not None:
        m = m * weight[label][:, None]
    mask = 1.0 - jax.nn.one_hot(label, c, dtype=input.dtype)
    loss = jnp.sum(m * mask, axis=1) / c
    return _reduce(loss, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    """Legacy fused op (paddle F.softmax_with_cross_entropy): returns
    UNREDUCED per-row loss with a trailing 1-dim, like the reference."""
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        squeeze_back = False
        if lbl.ndim == logits.ndim:
            lbl = lbl.squeeze(axis)
            squeeze_back = True
        safe = jnp.where(lbl == ignore_index, 0, lbl).astype(jnp.int32)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(safe, axis), axis)
        loss = jnp.where(jnp.expand_dims(lbl == ignore_index, axis),
                         0.0, -picked)
        if not squeeze_back:
            pass  # paddle keeps the trailing dim either way
    if return_softmax:
        return loss, jax.nn.softmax(logits, axis=axis)
    return loss


def feature_alpha_dropout(x, p=0.5, training=True):
    """alpha_dropout dropping whole feature maps (channel axis 1)."""
    if not training or p == 0.0:
        return x
    alpha_p = -1.7580993408473766
    shape = tuple(x.shape[i] if i < 2 else 1 for i in range(x.ndim))
    keep = jax.random.bernoulli(_random.split_key(), 1.0 - p, shape)
    a = ((1.0 - p) * (1.0 + p * alpha_p ** 2)) ** -0.5
    b = -a * p * alpha_p
    return a * jnp.where(jnp.broadcast_to(keep, x.shape), x, alpha_p) + b


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None):
    """1-D unpool through the 2-D path (single-row plane: the flat
    index is identical)."""
    out2d = max_unpool2d(
        x[:, :, None, :], indices[:, :, None, :],
        (1, _norm_tuple(kernel_size, 1)[0]),
        (1, _norm_tuple(stride if stride is not None else kernel_size,
                        1)[0]),
        (0, _norm_tuple(padding, 1)[0]),
        output_size=(1, output_size[-1]) if output_size else None)
    return out2d[:, :, 0, :]


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None):
    """Scatter pooled values back to argmax positions in a DHW volume."""
    k = _norm_tuple(kernel_size, 3)
    s = _norm_tuple(stride if stride is not None else kernel_size, 3)
    p = _norm_tuple(padding, 3)
    n, c, d, h, w = x.shape
    if output_size is None:
        od = (d - 1) * s[0] + k[0] - 2 * p[0]
        oh = (h - 1) * s[1] + k[1] - 2 * p[1]
        ow = (w - 1) * s[2] + k[2] - 2 * p[2]
    else:
        od, oh, ow = output_size[-3], output_size[-2], output_size[-1]
    flat = jnp.zeros((n, c, od * oh * ow), x.dtype)
    idx = indices.reshape(n, c, d * h * w).astype(jnp.int32)
    flat = flat.at[
        jnp.arange(n)[:, None, None], jnp.arange(c)[None, :, None],
        idx].set(x.reshape(n, c, d * h * w))
    return flat.reshape(n, c, od, oh, ow)


def class_center_sample(label, num_classes, num_samples):
    """paddle F.class_center_sample: keep every positive class center
    plus fill to num_samples with other classes; labels remapped into
    the sampled set.  Deterministic fill (ascending unsampled ids) —
    the reference samples uniformly; any fill set is a valid sample and
    determinism keeps the op jit-cacheable."""
    pos = jnp.zeros((num_classes,), jnp.bool_).at[label].set(True)
    # order: positives first (stable), then the rest; take num_samples
    order = jnp.argsort(~pos, stable=True)
    sampled = jax.lax.dynamic_slice_in_dim(order, 0, num_samples)
    # remap: position of each class id within `sampled`, -1 if absent
    inv = jnp.full((num_classes,), -1, jnp.int32).at[sampled].set(
        jnp.arange(num_samples, dtype=jnp.int32))
    return inv[label], sampled


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False,
                         reduction="mean"):
    """Combined-margin softmax CE (ArcFace family): the target-class
    cosine becomes cos(m1*theta + m2) - m3 before scaling.  logits must
    be cosines (normalized embeddings x normalized weights)."""
    cos = jnp.clip(logits, -1.0, 1.0)
    theta = jnp.arccos(cos)
    modified = jnp.cos(margin1 * theta + margin2) - margin3
    oh = jax.nn.one_hot(label, logits.shape[-1], dtype=logits.dtype)
    out = scale * (oh * modified + (1.0 - oh) * cos)
    logp = jax.nn.log_softmax(out, axis=-1)
    loss = -jnp.sum(oh * logp, axis=-1, keepdims=True)
    loss = _reduce(loss, reduction)
    if return_softmax:
        return loss, jax.nn.softmax(out, axis=-1)
    return loss


def adaptive_log_softmax_with_loss(input, label, head_weight,
                                   tail_weights, cutoffs,
                                   head_bias=None):
    """Adaptive softmax (Grave et al.): frequent classes in the head,
    rare ones in down-projected tail clusters.  Returns (output, loss)
    = (per-row target log-prob, its mean NLL), paddle's contract.

    TPU note: every row computes every cluster (masked), so the op is
    static-shaped and jit-safe — the host-side gather/scatter the
    reference uses per cluster would break under tracing here."""
    n_clusters = len(cutoffs)                  # tail clusters
    head_size = cutoffs[0] + n_clusters
    head = input @ head_weight
    if head_bias is not None:
        head = head + head_bias
    head_logp = jax.nn.log_softmax(head, axis=-1)
    lbl = label.astype(jnp.int32)
    # head part: classes < cutoffs[0]
    in_head = lbl < cutoffs[0]
    safe_head = jnp.where(in_head, lbl, 0)
    out = jnp.take_along_axis(head_logp, safe_head[:, None], 1)[:, 0]
    out = jnp.where(in_head, out, 0.0)
    for i, (proj, w) in enumerate(tail_weights):
        lo = cutoffs[i]
        hi = cutoffs[i + 1] if i + 1 < len(cutoffs) else lo + w.shape[-1]
        in_c = (lbl >= lo) & (lbl < hi)
        tail_logp = jax.nn.log_softmax(input @ proj @ w, axis=-1)
        safe = jnp.where(in_c, lbl - lo, 0)
        cluster_logit_pos = cutoffs[0] + i     # head slot of cluster i
        lp = (head_logp[:, cluster_logit_pos]
              + jnp.take_along_axis(tail_logp, safe[:, None], 1)[:, 0])
        out = jnp.where(in_c, lp, out)
    return out, -jnp.mean(out)
