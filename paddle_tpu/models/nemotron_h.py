"""Nemotron-H-style hybrid decoder: blocks that are ONE of a Mamba-2
(SSD) mixer, a softmax-attention mixer with no position signal, or a
latent sparse-expert layer (sigmoid router with a correction bias,
non-gated ``relu^2`` experts in a narrow latent, a shared expert at the
full width), in the order ``hybrid_override_pattern`` gives (``M``,
``*``, ``E``); each block is ``x + f(norm(x))``.

The equations are written out in ``models/references/nemotron_h.py``
(the plain float32 reference the tests and the benchmark compare with).
This module holds the weights under the names the serving engine's
backbone seam reads (``inference/backbone.py``) and an eager forward
pass that runs the SYSTEM's pieces — the chunked SSD scan of
``ops/pallas/mamba2_ssd.py`` and the serving expert layer
``inference/moe_dispatch.moe_ffn`` — over whole sequences.

The expert layer may hold a SHARE of the published experts
(``experts_held = (lo, hi)``): the router keeps its published width and
its top-k, the renormalisation runs over all k, and only the held
experts' matrices exist here; slots routed elsewhere add +0 before the
projection out of the latent.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from ..nn.common import Embedding, Linear
from ..nn.container import LayerList
from ..nn.initializer import Constant, Initializer, Normal, Uniform
from ..nn.layer import Layer
from ..ops._nn import rms_norm
from ..ops.pallas.mamba2_ssd import SUB, ssd_chunk, ssd_inputs, ssd_output
from ..tensor import Tensor, apply_op
# one block's weights out of a flat state dict, under the short names the
# serving engine's layer function, the eager forward and the plain
# reference share
from .references.nemotron_h import KINDS
from .references.nemotron_h import layer_params as layer_weights

__all__ = ["NemotronHConfig", "NemotronHForCausalLM",
           "nemotron_h_tiny_config"]


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512       # the router's (published) width
    num_experts_per_tok: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    # the share of the experts held here, [lo, hi); None = all
    experts_held: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 262144
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    @property
    def layer_kinds(self) -> tuple:
        assert len(self.hybrid_override_pattern) == self.num_hidden_layers
        return tuple(KINDS[p] for p in self.hybrid_override_pattern)

    @property
    def held(self) -> Tuple[int, int]:
        """(lo, n): first held expert and how many."""
        lo, hi = self.experts_held or (0, self.n_routed_experts)
        return int(lo), int(hi) - int(lo)

    @property
    def rms_norm_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


def nemotron_h_tiny_config(**kw) -> NemotronHConfig:
    """The CPU tests' size: every kind of block, 8 experts of which 4
    held, top-3; weights wide enough (std 0.15) that at these narrow
    widths the routed experts' ``relu^2`` still moves the logits."""
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=5,
                hybrid_override_pattern="MEM*E", num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
                mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                chunk_size=16, moe_intermediate_size=32,
                moe_latent_size=24,
                moe_shared_expert_intermediate_size=48,
                n_routed_experts=8, num_experts_per_tok=3,
                experts_held=(0, 4), max_position_embeddings=256,
                initializer_range=0.15)
    base.update(kw)
    return NemotronHConfig(**base)


class _LogUniform(Initializer):
    """``exp U(log lo, log hi)``; ``log=True`` keeps the logarithm (the
    Mamba-2 module's ``A_log``), ``inv_softplus=True`` returns ``dt +
    log(-expm1(-dt))`` with ``dt`` floored (its ``dt_bias``: softplus of
    it is a step size in ``[time_step_min, time_step_max]``)."""

    def __init__(self, lo, hi, log=False, inv_softplus=False, floor=0.0):
        self.lo, self.hi, self.log = lo, hi, log
        self.inv_softplus, self.floor = inv_softplus, floor

    def __call__(self, shape, dtype):
        import jax
        import jax.numpy as jnp

        from ..common.dtype import convert_dtype
        from ..ops import random as _random
        u = jax.random.uniform(_random.split_key(),
                               [int(s) for s in shape], jnp.float32,
                               math.log(self.lo), math.log(self.hi))
        if not self.log:
            u = jnp.exp(u)
        if self.inv_softplus:
            u = jnp.maximum(u, self.floor)
            u = u + jnp.log(-jnp.expm1(-u))
        return u.astype(convert_dtype(dtype))


class RMSNorm(Layer):
    def __init__(self, size: int, epsilon: float):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            [size], default_initializer=Constant(1.0))


class Mamba2Mixer(Layer):
    """The SSD mixer's weights; ``in_proj`` in plain blocks
    ``[z | x | B | C | dt]``."""

    def __init__(self, c: NemotronHConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        nh, k = c.mamba_num_heads, c.conv_kernel
        self.in_proj = Linear(c.hidden_size,
                              c.d_inner + c.conv_channels + nh,
                              weight_attr=init, bias_attr=False)
        self.conv_w = self.create_parameter(
            [k, c.conv_channels],
            default_initializer=Normal(0.0, 1.0 / math.sqrt(k)))
        self.conv_b = self.create_parameter(
            [c.conv_channels], default_initializer=Normal(0.0, 0.5))
        self.A_log = self.create_parameter(
            [nh], default_initializer=_LogUniform(1.0, 16.0, log=True))
        self.dt_bias = self.create_parameter(
            [nh], default_initializer=_LogUniform(
                c.time_step_min, c.time_step_max, inv_softplus=True,
                floor=c.time_step_floor))
        self.D = self.create_parameter(
            [nh], default_initializer=Uniform(0.5, 1.5))
        self.norm_w = self.create_parameter(
            [c.d_inner], default_initializer=Constant(1.0))
        self.out_proj = Linear(c.d_inner, c.hidden_size, weight_attr=init,
                               bias_attr=False)


class Attention(Layer):
    """The attention mixer's weights: no bias, no position signal."""

    def __init__(self, c: NemotronHConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        nh, kvh, hd = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim)
        self.q_proj = Linear(c.hidden_size, nh * hd, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kvh * hd, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kvh * hd, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(nh * hd, c.hidden_size, weight_attr=init,
                             bias_attr=False)


class _Router(Layer):
    def __init__(self, c: NemotronHConfig):
        super().__init__()
        self.num_experts, self.k = c.n_routed_experts, c.num_experts_per_tok
        self.norm_topk_prob = c.norm_topk_prob
        self.capacity_factor = 1.0            # dropless when served
        self.weight = self.create_parameter(
            [c.hidden_size, c.n_routed_experts],
            default_initializer=Normal(0.0, c.initializer_range))
        # HF starts this buffer at zero; seeded here so that leaving it
        # out of the selection changes what is chosen
        self.e_score_correction_bias = self.create_parameter(
            [c.n_routed_experts], default_initializer=Normal(0.0, 0.1))


class _LatentExperts(Layer):
    """The held experts' two matrices, in the latent."""

    def __init__(self, n: int, latent: int, width: int, std: float):
        super().__init__()
        init = Normal(0.0, std)
        self.up_w = self.create_parameter([n, latent, width],
                                          default_initializer=init)
        self.down_w = self.create_parameter([n, width, latent],
                                            default_initializer=init)


class LatentMoeShare(Layer):
    """Router over the published width; the projections into and out of
    the latent; the held experts; the shared expert."""

    def __init__(self, c: NemotronHConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        h, z = c.hidden_size, c.moe_latent_size
        fs = c.moe_shared_expert_intermediate_size
        self.gate = _Router(c)
        self.latent_in = Linear(h, z, weight_attr=init, bias_attr=False)
        self.latent_out = Linear(z, h, weight_attr=init, bias_attr=False)
        self.experts = _LatentExperts(c.held[1], z,
                                      c.moe_intermediate_size,
                                      c.initializer_range)
        self.shared_up = Linear(h, fs, weight_attr=init, bias_attr=False)
        self.shared_down = Linear(fs, h, weight_attr=init,
                                  bias_attr=False)


_MIXERS = {"ssm": Mamba2Mixer, "full": Attention, "ffn": LatentMoeShare}


class NemotronHBlock(Layer):
    """One norm and ONE of the three parts, under ``mixer``."""

    def __init__(self, c: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        self.mixer = _MIXERS[kind](c)


# -- the eager forward, in raw jax.numpy ------------------------------------------

def _ssm_mixer_seq(h, w, c: NemotronHConfig):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    d_in, cc = c.d_inner, c.conv_channels
    zxd = jnp.matmul(h, w["in_proj"])
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + cc], zxd[:, d_in + cc:]
    kw = c.conv_kernel
    xp = jnp.concatenate([jnp.zeros((kw - 1, cc), f32),
                          xbc.astype(f32)], 0)
    xbc = sum(xp[j:j + s] * w["conv"][j].astype(f32)[None, :]
              for j in range(kw)) + w["conv_bias"].astype(f32)[None, :]
    x, delta, a, b, cm = ssd_inputs(xbc, dt, w["A_log"], w["dt_bias"], c)
    sub = min(SUB, c.chunk_size)
    n = -(-s // sub)
    pad = n * sub - s

    def chunks(v):
        v = jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)], 0)
        return v.reshape((n, sub) + v.shape[1:])

    def step(state, xs):
        with jax.default_matmul_precision("highest"):
            y, state = ssd_chunk(xs[0], xs[1], a, xs[2], xs[3], state)
        return state, y
    _, y = jax.lax.scan(
        step, jnp.zeros((c.mamba_num_heads, c.mamba_head_dim,
                         c.ssm_state_size), f32),
        tuple(chunks(v) for v in (x, delta, b, cm)))
    y = y.reshape((n * sub,) + y.shape[2:])[:s] \
        + w["D"].astype(f32)[None, :, None] * x
    y = ssd_output(y, z, w["norm"], c.layer_norm_epsilon,
                   c.n_groups).astype(h.dtype)
    return jnp.matmul(y, w["o"])


def _full_mixer_seq(h, w, c: NemotronHConfig):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    nh, kvh, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = jnp.matmul(h, w["q"]).reshape(s, nh, hd).astype(f32)
    k = jnp.matmul(h, w["k"]).reshape(s, kvh, hd).astype(f32)
    v = jnp.matmul(h, w["v"]).reshape(s, kvh, hd).astype(f32)
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).astype(h.dtype)
    return jnp.matmul(o.reshape(s, nh * hd), w["o"])


def _forward_raw(ids, leaves, *, model):
    """ids [B, S] -> logits [B, S, V]; ``leaves`` the parameters in
    ``named_parameters`` order (so gradients flow), rebound by name."""
    import jax
    import jax.numpy as jnp

    from ..inference.moe_dispatch import moe_ffn
    c = model.config
    sd = dict(zip((k for k, _ in model.named_parameters()), leaves))
    arch = model.moe_arch("dense")
    eps = c.layer_norm_epsilon
    s = ids.shape[1]

    def one(seq):
        x = jnp.take(sd["embed_tokens.weight"], seq, axis=0)
        live = jnp.ones(s, bool)
        for i, kind in enumerate(c.layer_kinds):
            w = layer_weights(sd, i, kind)
            if kind == "ffn":
                ff, _ = moe_ffn(rms_norm(x, w["post_norm"], eps), w, arch,
                                live)
                x = x + ff
                continue
            h = rms_norm(x, w["in_norm"], eps)
            x = x + (_ssm_mixer_seq if kind == "ssm"
                     else _full_mixer_seq)(h, w, c)
        x = rms_norm(x, sd["norm.weight"], eps)
        return jnp.matmul(x, sd["lm_head.weight"])
    return jax.vmap(one)(ids)


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=Normal(0.0, c.initializer_range))
        self.layers = LayerList([NemotronHBlock(c, kind)
                                 for kind in c.layer_kinds])
        self.norm = RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        self.lm_head = Linear(c.hidden_size, c.vocab_size, bias_attr=False,
                              weight_attr=Normal(0.0, c.initializer_range))
        # no position signal anywhere: rotary tables of width zero
        empty = np.zeros((c.max_position_embeddings, 0), np.float32)
        self.register_buffer("rope_cos", Tensor(empty), persistable=False)
        self.register_buffer("rope_sin", Tensor(empty), persistable=False)

    def moe_arch(self, dispatch: str = "grouped"):
        from ..inference.moe_dispatch import MoEArch
        c = self.config
        lo, n = c.held
        return MoEArch(num_experts=c.n_routed_experts,
                       top_k=c.num_experts_per_tok,
                       norm_topk=c.norm_topk_prob, capacity=0, shared=True,
                       shared_gate=False, attn_bias=False,
                       dispatch=dispatch, expert_lo=lo, experts_held=n,
                       scoring="sigmoid",
                       route_scale=float(c.routed_scaling_factor),
                       expert_act="relu2")

    def serving_layer_weights(self) -> tuple:
        """One weight dict a block (``layer_weights``'s short names)
        whose leaves are this model's own arrays: what the serving
        engine's layer loop uses, so the expert matrices exist once."""
        sd = self.raw_state_dict()
        return tuple(layer_weights(sd, i, kind)
                     for i, kind in enumerate(self.config.layer_kinds))

    def forward(self, input_ids):
        return apply_op(_forward_raw, input_ids,
                        [p for _, p in self.named_parameters()],
                        model=self)
