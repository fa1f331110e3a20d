"""Qwen3-Next-style hybrid decoder: Gated-DeltaNet linear-attention
layers and gated full-attention layers in a fixed period (3 linear : 1
full at ``full_attention_interval`` 4), every layer followed by a
sparse-expert FFN with a gated shared expert.

The equations are written out in ``models/references/qwen3_next.py``
(the plain float32 reference the tests and the benchmark compare with).
This module holds the weights under the names the serving engine's
backbone seam reads (``inference/backbone.py``) and an eager forward
pass that runs the SYSTEM's pieces — the chunked (WY) Gated-DeltaNet of
``ops/pallas/gated_delta.py`` and the serving expert layer
``inference/moe_dispatch.moe_ffn`` — over whole sequences.

The expert layer may hold a SHARE of the published experts
(``experts_held = (lo, hi)``): the router keeps its published width and
its top-k, the renormalisation runs over all k, and only the held
experts' matrices exist here; slots routed elsewhere add +0.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from ..nn.common import Embedding, Linear
from ..nn.container import LayerList
from ..nn.initializer import Constant, Initializer, Normal
from ..nn.layer import Layer
from ..nn.moe import ExpertFFN
from ..tensor import Tensor, apply_op
from ..ops._nn import rms_norm_zero_centred as norm0
from ..ops.pallas.gated_delta import gdn_inputs, gdn_output
from .llama import _rope_cos_sin
# one layer's weights out of a flat state dict, under the short names the
# serving engine's layer function, the eager forward and the plain
# reference share
from .references.qwen3_next import layer_params as layer_weights

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM",
           "qwen3_next_tiny_config"]


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512            # the router's (published) width
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    # the share of the experts held here, [lo, hi); None = all
    experts_held: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    @property
    def layer_kinds(self) -> tuple:
        return tuple(
            "full" if (i + 1) % self.full_attention_interval == 0
            else "linear" for i in range(self.num_hidden_layers))

    @property
    def held(self) -> Tuple[int, int]:
        """(lo, n): first held expert and how many."""
        lo, hi = self.experts_held or (0, self.num_experts)
        return int(lo), int(hi) - int(lo)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)


def qwen3_next_tiny_config(**kw) -> Qwen3NextConfig:
    """The CPU tests' size: one period, 8 experts of which 4 held."""
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                linear_num_key_heads=2, linear_num_value_heads=4,
                linear_key_head_dim=8, linear_value_head_dim=8,
                moe_intermediate_size=32,
                shared_expert_intermediate_size=32, num_experts=8,
                num_experts_per_tok=2, experts_held=(0, 4),
                max_position_embeddings=256, rope_theta=10000.0)
    base.update(kw)
    return Qwen3NextConfig(**base)


class _LogUniform(Initializer):
    """``log U(lo, hi)`` — HF's ``A_log`` (so that the decay
    ``exp(-exp(A_log) softplus(.))`` is neither 0 nor 1)."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        import jax
        import jax.numpy as jnp

        from ..common.dtype import convert_dtype
        from ..ops import random as _random
        u = jax.random.uniform(_random.split_key(),
                               [int(s) for s in shape], jnp.float32,
                               self.lo, self.hi)
        return jnp.log(u).astype(convert_dtype(dtype))


class ZeroCentredRMSNorm(Layer):
    """``x / rms(x) * (1 + w)`` in float32; ``w`` starts at zero."""

    def __init__(self, size: int, epsilon: float):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            [size], default_initializer=Constant(0.0))


class GatedDeltaNet(Layer):
    """The linear-attention mixer's weights."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        nk = c.linear_num_key_heads * c.linear_key_head_dim
        nv = c.linear_num_value_heads * c.linear_value_head_dim
        hv = c.linear_num_value_heads
        # plain blocks [q | k | v | z] and [b | a] (HF interleaves them
        # per key-head group: a permutation of columns)
        self.in_proj_qkvz = Linear(c.hidden_size, 2 * nk + 2 * nv,
                                   weight_attr=init, bias_attr=False)
        self.in_proj_ba = Linear(c.hidden_size, 2 * hv,
                                 weight_attr=init, bias_attr=False)
        k = c.linear_conv_kernel_dim
        self.conv_w = self.create_parameter(
            [k, c.conv_channels],
            default_initializer=Normal(0.0, 1.0 / math.sqrt(k)))
        self.A_log = self.create_parameter(
            [hv], default_initializer=_LogUniform(1e-3, 16.0))
        self.dt_bias = self.create_parameter(
            [hv], default_initializer=Constant(1.0))
        self.norm_w = self.create_parameter(
            [c.linear_value_head_dim], default_initializer=Constant(1.0))
        self.out_proj = Linear(nv, c.hidden_size, weight_attr=init,
                               bias_attr=False)


class GatedAttention(Layer):
    """The full-attention mixer's weights: q carries its output gate."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        nh, kvh, hd = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim)
        self.q_proj = Linear(c.hidden_size, nh * 2 * hd, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kvh * hd, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kvh * hd, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(nh * hd, c.hidden_size, weight_attr=init,
                             bias_attr=False)
        self.q_norm = ZeroCentredRMSNorm(hd, c.rms_norm_eps)
        self.k_norm = ZeroCentredRMSNorm(hd, c.rms_norm_eps)


class _Router(Layer):
    def __init__(self, hidden: int, num_experts: int, k: int,
                 norm_topk_prob: bool, std: float):
        super().__init__()
        self.num_experts, self.k = num_experts, k
        self.norm_topk_prob = norm_topk_prob
        self.capacity_factor = 1.0            # dropless when served
        self.weight = self.create_parameter(
            [hidden, num_experts], default_initializer=Normal(0.0, std))


class SparseMoeShare(Layer):
    """Router over the published width; the held experts' matrices; the
    gated shared expert."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        _, n_held = c.held
        self.gate = _Router(c.hidden_size, c.num_experts,
                            c.num_experts_per_tok, c.norm_topk_prob,
                            c.initializer_range)
        self.experts = ExpertFFN(n_held, c.hidden_size,
                                 c.moe_intermediate_size,
                                 init_std=c.initializer_range)
        f = c.shared_expert_intermediate_size
        self.shared_gate = Linear(c.hidden_size, f, weight_attr=init,
                                  bias_attr=False)
        self.shared_up = Linear(c.hidden_size, f, weight_attr=init,
                                bias_attr=False)
        self.shared_down = Linear(f, c.hidden_size, weight_attr=init,
                                  bias_attr=False)
        self.shared_expert_gate = Linear(c.hidden_size, 1,
                                         weight_attr=init, bias_attr=False)


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, c: Qwen3NextConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.input_layernorm = ZeroCentredRMSNorm(c.hidden_size,
                                                  c.rms_norm_eps)
        if kind == "linear":
            self.linear_attn = GatedDeltaNet(c)
        else:
            self.self_attn = GatedAttention(c)
        self.post_attention_layernorm = ZeroCentredRMSNorm(
            c.hidden_size, c.rms_norm_eps)
        self.mlp = SparseMoeShare(c)


# -- the eager forward, in raw jax.numpy ------------------------------------------

def rope_partial(x, cos, sin):
    """x [.., D]; cos/sin broadcastable [.., rot]: half rotation over
    the first ``rot`` dims, the rest passes (float32 in, float32 out)."""
    import jax.numpy as jnp
    rot = cos.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr, xp], -1)


def _linear_mixer_seq(h, w, c: Qwen3NextConfig):
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.gated_delta import SUB, gated_delta_chunk
    f32 = jnp.float32
    s = h.shape[0]
    hv, dk, dv = (c.linear_num_value_heads, c.linear_key_head_dim,
                  c.linear_value_head_dim)
    cc = c.conv_channels
    qkvz = jnp.matmul(h, w["qkvz"])
    ba = jnp.matmul(h, w["ba"])
    mixed, z = qkvz[:, :cc].astype(f32), qkvz[:, cc:]
    kw = c.linear_conv_kernel_dim
    xp = jnp.concatenate([jnp.zeros((kw - 1, cc), f32), mixed], 0)
    mixed = sum(xp[j:j + s] * w["conv"][j].astype(f32)[None, :]
                for j in range(kw))
    q, k, v, g, beta = gdn_inputs(mixed, ba[:, :hv], ba[:, hv:],
                                  w["A_log"], w["dt_bias"], c)
    n = -(-s // SUB)
    pad = n * SUB - s

    def chunks(x):
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
        return x.reshape((n, SUB) + x.shape[1:])

    def step(state, xs):
        with jax.default_matmul_precision("highest"):
            o, state = gated_delta_chunk(*xs, state)
        return state, o
    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), f32),
                        tuple(chunks(x) for x in (q, k, v, g, beta)))
    o = o.reshape((n * SUB,) + o.shape[2:])[:s]
    y = gdn_output(o, z, w["norm"], c.rms_norm_eps).astype(h.dtype)
    return jnp.matmul(y, w["o"])


def _full_mixer_seq(h, w, cos, sin, c: Qwen3NextConfig):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    nh, kvh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    qg = jnp.matmul(h, w["q"]).reshape(s, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = jnp.matmul(h, w["k"]).reshape(s, kvh, hd)
    v = jnp.matmul(h, w["v"]).reshape(s, kvh, hd)
    q = norm0(q, w["q_norm"], c.rms_norm_eps).astype(f32)
    k = norm0(k, w["k_norm"], c.rms_norm_eps).astype(f32)
    q = rope_partial(q, cos[:, None, :], sin[:, None, :])
    k = rope_partial(k, cos[:, None, :], sin[:, None, :])
    k = jnp.repeat(k, nh // kvh, axis=1)
    vv = jnp.repeat(v.astype(f32), nh // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, vv)
    o = (o * jax.nn.sigmoid(gate.astype(f32))).astype(h.dtype)
    return jnp.matmul(o.reshape(s, nh * hd), w["o"])


def _forward_raw(ids, leaves, *, model):
    """ids [B, S] -> logits [B, S, V]; ``leaves`` the parameters in
    ``named_parameters`` order (so gradients flow), rebound by name."""
    import jax
    import jax.numpy as jnp

    from ..inference.moe_dispatch import moe_ffn
    c = model.config
    names = [k for k, _ in model.named_parameters()]
    sd = dict(zip(names, leaves))
    arch = model.moe_arch("dense")
    s = ids.shape[1]
    cos = jnp.asarray(model.rope_cos.value)[:s]
    sin = jnp.asarray(model.rope_sin.value)[:s]

    def one(seq):
        x = jnp.take(sd["embed_tokens.weight"], seq, axis=0)
        live = jnp.ones(s, bool)
        for i, kind in enumerate(c.layer_kinds):
            w = layer_weights(sd, i, kind)
            h = norm0(x, w["in_norm"], c.rms_norm_eps)
            if kind == "linear":
                x = x + _linear_mixer_seq(h, w, c)
            else:
                x = x + _full_mixer_seq(h, w, cos, sin, c)
            h = norm0(x, w["post_norm"], c.rms_norm_eps)
            ff, _ = moe_ffn(h, moe_weights(w), arch, live)
            x = x + ff
        x = norm0(x, sd["norm.weight"], c.rms_norm_eps)
        return jnp.matmul(x, sd["lm_head.weight"])
    return jax.vmap(one)(ids)


def moe_weights(w: dict) -> tuple:
    """``moe_ffn``'s weight tuple from a layer's dict."""
    return (w["router"], w["experts_gate"], w["experts_up"],
            w["experts_down"], w["shared_gate"], w["shared_up"],
            w["shared_down"], w["shared_expert_gate"])


class Qwen3NextForCausalLM(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=Normal(0.0, c.initializer_range))
        self.layers = LayerList([Qwen3NextDecoderLayer(c, kind)
                                 for kind in c.layer_kinds])
        self.norm = ZeroCentredRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = Linear(c.hidden_size, c.vocab_size, bias_attr=False,
                              weight_attr=Normal(0.0, c.initializer_range))
        rope = _rope_cos_sin(c.max_position_embeddings, c.rotary_dim,
                             c.rope_theta)
        self.register_buffer("rope_cos", Tensor(np.cos(rope)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(np.sin(rope)),
                             persistable=False)

    def moe_arch(self, dispatch: str = "grouped"):
        from ..inference.moe_dispatch import MoEArch
        c = self.config
        lo, n = c.held
        return MoEArch(num_experts=c.num_experts,
                       top_k=c.num_experts_per_tok,
                       norm_topk=c.norm_topk_prob, capacity=0, shared=True,
                       shared_gate=True, attn_bias=False,
                       dispatch=dispatch, expert_lo=lo, experts_held=n)

    def serving_layer_weights(self) -> tuple:
        """One weight dict a layer (``layer_weights``'s short names) whose
        leaves are this model's own arrays: what the serving engine's
        layer loop uses, so the expert matrices exist once."""
        sd = self.raw_state_dict()
        return tuple(layer_weights(sd, i, kind)
                     for i, kind in enumerate(self.config.layer_kinds))

    def forward(self, input_ids):
        return apply_op(_forward_raw, input_ids,
                        [p for _, p in self.named_parameters()],
                        model=self)
