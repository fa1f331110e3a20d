"""Llama-3 model family (configs #2/#3 of BASELINE.json).

Reference parity: PaddleNLP llm/ Llama pretraining recipe (the reference's
headline benchmark: Llama-3-8B tokens/sec/chip, BASELINE.md) — RMSNorm,
rotary embeddings, GQA attention, SwiGLU MLP, tied/untied LM head.

TPU-native design: weights carry ``dist_spec`` mesh-axis annotations
(Megatron layout: qkv/gate/up column-sharded, o/down row-sharded over
``mp``; embeddings vocab-sharded) so the SAME model runs 1-chip or on any
(dp, sharding, mp, sep) mesh — GSPMD emits the collectives.  Attention
routes through the fused flash path (F.scaled_dot_product_attention).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import ops as P
from ..nn import functional as F
from ..nn.common import Embedding, Linear
from ..nn.container import LayerList
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..nn.generation import (GenerationMixin, StaticCache,
                             cached_attention_raw, write_cache_raw)
from ..nn.norm import RMSNorm
from ..tensor import Tensor, apply_op

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaForCausalLMPipe", "LlamaPretrainingCriterion",
           "llama3_8b_config", "llama_tiny_config", "apply_rotary_pos_emb"]


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    attention_bias: bool = False     # qkv/o biases (Qwen2-family True)
    rope_interleaved: bool = False   # GPT-J pairing (ERNIE-4.5 True)
    fuse_qkv: bool = False           # single qkv matmul (concat weights)
    # fused step regions (ops/pallas/fused_train): rope applied in the
    # q/k projections' output write + residual-add fused into the
    # post-attention RMSNorm.  Bit-identical to False (the unfused
    # chain) — kernels engage on TPU only
    fuse_norm_rope: bool = True
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    recompute: bool = False
    recompute_granularity: str = "full"   # "full" | "core_attn" | "dots"
    fuse_linear_cross_entropy: bool = True  # chunked lm_head+CE (training)
    # 1F1B keeps in-flight VJP residuals instead of recomputing the
    # stage forward at each backward tick (measured 1.26x faster per
    # microbatch-stage at the 770m bench shape on v5e; costs residual
    # ring memory ∝ pp — set False when HBM-bound)
    pp_stash_residuals: bool = True


def llama3_8b_config() -> LlamaConfig:
    return LlamaConfig()


def llama_tiny_config() -> LlamaConfig:
    return LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128,
                       rope_theta=10000.0)


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float,
                  dtype=np.float32):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                          dtype=np.float64) / head_dim))
    t = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(t, inv_freq)                      # [S, D/2]
    emb = np.concatenate([freqs, freqs], axis=-1)      # [S, D]
    return emb.astype(dtype)


def _rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate_half_interleaved(x):
    """GPT-J-style pairing over (even, odd) lanes — the ERNIE-4.5
    convention (its cos/sin stay in the llama cat(freqs, freqs)
    layout)."""
    import jax.numpy as jnp
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def _apply_rope_raw(q, k, cos, sin, interleaved: bool = False):
    """q/k: [B, S, H, D]; cos/sin: [S, D] in the cat(freqs, freqs)
    layout (f32 compute).  ``interleaved`` applies the GLM/ERNIE-4.5
    convention: lanes pair as (2i, 2i+1) and BOTH use angle θ_i, so the
    angles are repeat_interleaved from the first half."""
    import jax.numpy as jnp
    if interleaved:
        half = cos.shape[-1] // 2
        cos = jnp.repeat(cos[..., :half], 2, axis=-1)
        sin = jnp.repeat(sin[..., :half], 2, axis=-1)
    rot = _rotate_half_interleaved if interleaved else _rotate_half
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    q_out = qf * cos + rot(qf) * sin
    k_out = kf * cos + rot(kf) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)


def apply_rotary_pos_emb(q, k, cos, sin, interleaved: bool = False):
    return apply_op(_apply_rope_raw, q, k, cos, sin,
                    interleaved=interleaved)


def _seq_parallel_raw(x):
    """Pin hidden states [B,S,H] to batch-over-(dp,sharding) and
    seq-over-sep — the Megatron-SP/context-parallel activation layout;
    GSPMD reshards attention around it (fleet sequence_parallel_utils
    analog)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..distributed.auto_parallel import get_mesh
    pm = get_mesh()
    if pm is None or pm.mesh.shape.get("sep", 1) <= 1:
        return x
    # drop axes the dims cannot divide over (mirrors sharding.py's
    # plan_param_spec behavior instead of failing at runtime — ADVICE.md r1)
    shape = pm.mesh.shape
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if shape.get(a, 1) > 1)
    import math as _math
    if batch_axes and x.shape[0] % _math.prod(
            shape[a] for a in batch_axes):
        batch_axes = ()
    seq_axis = "sep" if x.shape[1] % shape["sep"] == 0 else None
    if not batch_axes and seq_axis is None:
        return x
    spec = PartitionSpec(batch_axes if batch_axes else None, seq_axis, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pm.mesh, spec))


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        init = Normal(0.0, c.initializer_range)
        out_init = Normal(0.0, c.initializer_range /
                          math.sqrt(2 * c.num_hidden_layers))
        qkv_bias = getattr(c, "attention_bias", False)
        self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                             weight_attr=init, bias_attr=qkv_bias)
        self.k_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=qkv_bias)
        self.v_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             weight_attr=init, bias_attr=qkv_bias)
        self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                             weight_attr=out_init, bias_attr=False)
        # Megatron TP layout
        self.q_proj.weight.dist_spec = (None, "mp")
        self.k_proj.weight.dist_spec = (None, "mp")
        self.v_proj.weight.dist_spec = (None, "mp")
        self.o_proj.weight.dist_spec = ("mp", None)
        self.use_flash = config.use_flash_attention
        self.rope_interleaved = getattr(config, "rope_interleaved", False)
        self.fuse_qkv = getattr(config, "fuse_qkv", False)
        self.fuse_norm_rope = getattr(config, "fuse_norm_rope", True)

    def forward(self, x, cos_sin, cache=None, pos=None, prefill=False):
        b, s, _ = x.shape
        cos, sin = cos_sin
        # fused chain needs raw projection weights: a quantize_model'd
        # attention (QuantizedLinear: qweight+scales, no .weight) takes
        # the module-call path below
        fuse_rope = (self.fuse_norm_rope and not self.fuse_qkv
                     and getattr(self.q_proj, "bias", None) is None
                     and all(getattr(p, "weight", None) is not None
                             for p in (self.q_proj, self.k_proj,
                                       self.v_proj)))
        if fuse_rope:
            # fused rotary→QKV chain: rope rides the projection's output
            # write (one pass per projection on TPU; bit-identical jnp
            # composition elsewhere)
            q, k, v = F.qkv_rope(
                x, self.q_proj.weight, self.k_proj.weight,
                self.v_proj.weight, cos, sin, n_heads=self.num_heads,
                n_kv=self.num_kv_heads, head_dim=self.head_dim,
                interleaved=self.rope_interleaved)
        elif self.fuse_qkv:
            # one [H, (nh+2*nkv)*hd] matmul: the weight concat is cheap
            # relative to the fused MXU pass (weights stay separate
            # Parameters for checkpoint/TP-spec compatibility)
            nq = self.num_heads * self.head_dim
            nkv = self.num_kv_heads * self.head_dim
            w = P.concat([self.q_proj.weight, self.k_proj.weight,
                          self.v_proj.weight], axis=1)
            qkv = P.matmul(x, w)
            if self.q_proj.bias is not None:
                bias = P.concat([self.q_proj.bias, self.k_proj.bias,
                                 self.v_proj.bias], axis=0)
                qkv = qkv + bias
            q = P.reshape(qkv[:, :, :nq],
                          [b, s, self.num_heads, self.head_dim])
            k = P.reshape(qkv[:, :, nq:nq + nkv],
                          [b, s, self.num_kv_heads, self.head_dim])
            v = P.reshape(qkv[:, :, nq + nkv:],
                          [b, s, self.num_kv_heads, self.head_dim])
        else:
            q = P.reshape(self.q_proj(x),
                          [b, s, self.num_heads, self.head_dim])
            k = P.reshape(self.k_proj(x),
                          [b, s, self.num_kv_heads, self.head_dim])
            v = P.reshape(self.v_proj(x),
                          [b, s, self.num_kv_heads, self.head_dim])
        if not fuse_rope:
            # the fused chain above already applied rope in-register
            q, k = apply_rotary_pos_emb(q, k, cos, sin,
                                        interleaved=self.rope_interleaved)
        attn_fn = (F.scaled_dot_product_attention if self.use_flash
                   else F.scaled_dot_product_attention_ref)
        if pos is not None:
            # static-cache decode protocol (nn/generation.py): fixed-size
            # buffers, in-place writes — every step one compiled shape
            if prefill and s > 1:
                # caller guarantees pos == 0 (GenerationMixin's first
                # call): attention is plain causal over the prompt, flash
                # eligible; chunked prefill (pos>0) takes the generic path
                out = attn_fn(q, k, v, is_causal=True)
                kb, vb = apply_op(write_cache_raw, k, v, cache.k, cache.v,
                                  pos)
            else:
                out, kb, vb = apply_op(cached_attention_raw, q, k, v,
                                       cache.k, cache.v, pos)
            out = P.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), StaticCache(kb, vb)
        if cache is not None:
            k = P.concat([cache[0], k], axis=1)
            v = P.concat([cache[1], v], axis=1)
        out = attn_fn(q, k, v, is_causal=True)
        out = P.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if cache is not None:
            return out, (k, v)
        return out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        out_init = Normal(0.0, c.initializer_range /
                          math.sqrt(2 * c.num_hidden_layers))
        self.gate_proj = Linear(c.hidden_size, c.intermediate_size,
                                weight_attr=init, bias_attr=False)
        self.up_proj = Linear(c.hidden_size, c.intermediate_size,
                              weight_attr=init, bias_attr=False)
        self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                weight_attr=out_init, bias_attr=False)
        self.gate_proj.weight.dist_spec = (None, "mp")
        self.up_proj.weight.dist_spec = (None, "mp")
        self.down_proj.weight.dist_spec = ("mp", None)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self._fuse_chain = getattr(config, "fuse_norm_rope", True)

    def _post_attn(self, x, attn):
        """residual-add + post-attention RMSNorm + MLP residual."""
        if self._fuse_chain:
            # fused residual→RMSNorm: the attn-residual write and the
            # norm read share one pass (bit-identical to the unfused
            # chain below)
            x, hn = self.post_attention_layernorm.forward_residual(attn, x)
            return x + self.mlp(hn)
        x = x + attn
        return x + self.mlp(self.post_attention_layernorm(x))

    def forward(self, x, cos_sin, cache=None, pos=None, prefill=False):
        if cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x),
                                             cos_sin, cache, pos=pos,
                                             prefill=prefill)
            return self._post_attn(x, attn), new_cache
        attn = self.self_attn(self.input_layernorm(x), cos_sin)
        # named residual for selective remat (recompute_granularity
        # "core_attn": keep the flash output, recompute the cheap rest)
        attn = apply_op(_ckpt_name_attn, attn)
        return self._post_attn(x, attn)


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        self.embed_tokens.weight.dist_spec = ("mp", None)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        rope = _rope_cos_sin(config.max_position_embeddings, head_dim,
                             config.rope_theta)
        self.register_buffer("rope_cos", Tensor(np.cos(rope)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(np.sin(rope)),
                             persistable=False)

    def _cos_sin(self, start: int, seq_len: int):
        cos = self.rope_cos[start:start + seq_len]
        sin = self.rope_sin[start:start + seq_len]
        return cos, sin

    def _cos_sin_at(self, pos, seq_len: int):
        """RoPE tables gathered at traced positions pos..pos+seq_len."""
        def gather(cos_t, sin_t, p, *, s):
            import jax.numpy as jnp
            idx = p.astype(jnp.int32) + jnp.arange(s)
            return jnp.take(cos_t, idx, axis=0), jnp.take(sin_t, idx, axis=0)
        return apply_op(gather, self.rope_cos, self.rope_sin, pos, s=seq_len)

    def forward(self, input_ids, caches=None, pos=None, prefill=False):
        b, s = input_ids.shape
        x = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            x = apply_op(_seq_parallel_raw, x)
        if pos is not None:
            cos_sin = self._cos_sin_at(pos, s)
            new_caches = []
            for i, layer in enumerate(self.layers):
                x, c = layer(x, cos_sin, caches[i], pos=pos,
                             prefill=prefill)
                new_caches.append(c)
            return self.norm(x), new_caches
        past = 0 if caches is None else (
            caches[0][0].shape[1] if caches[0] is not None else 0)
        cos_sin = self._cos_sin(past, s)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, cos_sin, caches[i])
                new_caches.append(c)
            elif self.config.recompute:
                from ..jit.recompute import recompute
                gran = self.config.recompute_granularity
                x = recompute(layer, x, cos_sin,
                              policy=None if gran == "full" else gran)
            else:
                x = layer(x, cos_sin)
        x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False,
                                  weight_attr=Normal(
                                      0.0, config.initializer_range))
            self.lm_head.weight.dist_spec = (None, "mp")

    # HF-style alias used by recipes
    @property
    def model(self):
        return self.llama

    def forward(self, input_ids, caches=None, labels=None, pos=None,
                prefill=False):
        if pos is not None:
            hidden, new_caches = self.llama(input_ids, caches, pos=pos,
                                            prefill=prefill)
            if self.lm_head is None:
                logits = P.matmul(hidden, self.llama.embed_tokens.weight,
                                  transpose_y=True)
            else:
                logits = self.lm_head(hidden)
            return logits, new_caches
        out = self.llama(input_ids, caches)
        hidden = out[0] if caches is not None else out
        if labels is not None and self.config.fuse_linear_cross_entropy:
            # training fast path: never materializes [B,S,V] logits
            if self.lm_head is None:
                loss = F.fused_linear_cross_entropy(
                    hidden, self.llama.embed_tokens.weight, labels,
                    transpose_weight=True)
            else:
                loss = F.fused_linear_cross_entropy(
                    hidden, self.lm_head.weight, labels)
            return (loss, out[1]) if caches is not None else loss
        if self.lm_head is None:
            logits = P.matmul(hidden, self.llama.embed_tokens.weight,
                              transpose_y=True)
        else:
            logits = self.lm_head(hidden)
        if labels is not None:
            loss = LlamaPretrainingCriterion()(logits, labels)
            return (loss, out[1]) if caches is not None else loss
        if caches is not None:
            return logits, out[1]
        return logits

    def gen_caches(self, batch_size: int):
        c = self.config
        hd = c.hidden_size // c.num_attention_heads
        return [(P.zeros([batch_size, 0, c.num_key_value_heads, hd]),
                 P.zeros([batch_size, 0, c.num_key_value_heads, hd]))
                for _ in range(c.num_hidden_layers)]

    def gen_static_caches(self, batch_size: int, total_len: int):
        """Fixed-size decode buffers (GenerationMixin protocol)."""
        from ..common.errors import enforce
        c = self.config
        enforce(total_len <= c.max_position_embeddings,
                f"prompt + max_new_tokens = {total_len} exceeds "
                f"max_position_embeddings = {c.max_position_embeddings} "
                "(the RoPE table would clamp and rotations would be wrong)")
        hd = c.hidden_size // c.num_attention_heads
        dt = self.llama.embed_tokens.weight.dtype
        return [StaticCache(
            P.zeros([batch_size, total_len, c.num_key_value_heads, hd],
                    dtype=dt),
            P.zeros([batch_size, total_len, c.num_key_value_heads, hd],
                    dtype=dt))
            for _ in range(c.num_hidden_layers)]


def _attn_for_shape(q, k, v):
    """Flash kernel when eligible, jnp oracle otherwise — both raw
    (callable inside shard_map/scan).  Eligibility is owned by
    flash_attention_raw itself (single source of the shape rules)."""
    from ..common.flags import get_flag
    from ..runtime.device import is_compiled_with_tpu
    if get_flag("use_pallas") and is_compiled_with_tpu():
        from ..ops.pallas import ShapeNotCovered
        from ..ops.pallas.spmd import flash_attention_spmd
        try:
            return flash_attention_spmd(q, k, v, causal=True)
        except ShapeNotCovered:
            pass
    from ..ops import _nn
    return _nn.scaled_dot_product_attention(q, k, v, is_causal=True)


def _ckpt_name_attn(a):
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(a, "attn_out")


def _decoder_layer_raw(lp, h, cos, sin, *, n_heads, n_kv, head_dim, eps,
                       rope_interleaved=False):
    """One Llama decoder layer on raw arrays (mirrors LlamaDecoderLayer;
    kept in sync by the pipe-vs-sequential parity test)."""
    import jax.numpy as jnp

    from ..ops import _nn
    iln, qw, kw, vw, ow, pln, gw, uw, dw = lp
    b, s, _ = h.shape
    hn = _nn.rms_norm(h, iln, epsilon=eps)
    q = jnp.matmul(hn, qw).reshape(b, s, n_heads, head_dim)
    k = jnp.matmul(hn, kw).reshape(b, s, n_kv, head_dim)
    v = jnp.matmul(hn, vw).reshape(b, s, n_kv, head_dim)
    q, k = _apply_rope_raw(q, k, cos, sin,
                           interleaved=rope_interleaved)
    attn = _attn_for_shape(q, k, v).reshape(b, s, n_heads * head_dim)
    attn = _ckpt_name_attn(attn)
    h = h + jnp.matmul(attn, ow)
    hn = _nn.rms_norm(h, pln, epsilon=eps)
    ff = _nn.silu(jnp.matmul(hn, gw)) * jnp.matmul(hn, uw)
    return h + jnp.matmul(ff, dw)


@functools.lru_cache(maxsize=32)
def _pipe_stage_fn(n_heads, n_kv, head_dim, eps, rope_interleaved=False,
                   remat_policy=None):
    """Stable per-config stage callable (the pipeline engine caches its
    compiled form keyed on this object).

    ``remat_policy``: None = no remat; "full" = jax.checkpoint each
    layer; "core_attn"/"dots" = the jit/recompute.py named policies.
    This is what config.recompute means INSIDE a pipeline stage — with
    residual-stash 1F1B it also sets what the ring slots hold (the vjp
    residuals of the checkpointed layer are just the policy's saveable
    set), so core_attn shrinks the ring from full per-layer
    intermediates to flash out+lse + layer inputs."""
    import jax

    def layer_fn(lp, h, cos, sin):
        return _decoder_layer_raw(
            lp, h, cos, sin, n_heads=n_heads, n_kv=n_kv,
            head_dim=head_dim, eps=eps,
            rope_interleaved=rope_interleaved)

    if remat_policy is not None:
        from ..jit.recompute import _resolve_policy
        pol = _resolve_policy(None if remat_policy == "full"
                              else remat_policy)
        layer_fn = jax.checkpoint(layer_fn, policy=pol)

    def stage_fn(locals_, h, cos, sin):
        def body(h, lp):
            return layer_fn(lp, h, cos, sin), None
        h, _ = jax.lax.scan(body, h, tuple(locals_))
        return h

    return stage_fn


@functools.lru_cache(maxsize=32)
def _pipe_tail_fn(eps, transpose_head, ignore_index):
    """Loss head applied per microbatch on the LAST pipeline stage
    (reference: fleet PipelineParallel runs _loss_fn on the final stage
    only) — final RMSNorm + chunked fused linear+CE; returns
    (loss_sum, valid_token_count) so the engine psums scalars instead
    of gathering whole-batch activations."""
    import jax.numpy as jnp

    from ..ops import _nn

    def tail_fn(tail_params, y, labels_mb):
        norm_w, head_w = tail_params
        hn = _nn.rms_norm(y, norm_w, epsilon=eps)
        loss_sum = _nn.fused_linear_cross_entropy(
            hn, head_w, labels_mb, ignore_index=ignore_index,
            reduction="sum", transpose_weight=transpose_head)
        count = jnp.sum((labels_mb != ignore_index).astype(jnp.float32))
        return loss_sum, count

    return tail_fn


def _pipe_n_layers(p, n_virtual):
    """Layer count of a stacked pipe param: [L, ...] when v==1,
    [S, v, per, ...] interleaved storage when v>1."""
    return p.shape[0] if n_virtual == 1 \
        else p.shape[0] * p.shape[1] * p.shape[2]


def _pipe_layer_view(params, n_virtual, n_layers):
    """Global layer-order [L, ...] view of the stacks for the serial
    (no-mesh) path.  v>1 storage is [S(d), v(lap), per, ...] with chunk
    c = lap*S + d, so layer order = swap the (d, lap) dims and flatten
    — a host-cheap transpose on unsharded arrays."""
    import jax.numpy as jnp
    if n_virtual == 1:
        return list(params)
    return [jnp.swapaxes(p, 0, 1).reshape((n_layers,) + p.shape[3:])
            for p in params]


def _pipe_chunked(params, num_stages, n_virtual, n_layers):
    """Engine-layout chunk stacks: v==1 reshapes [L] -> [S, per] (an
    efficient dim-0 split of the pp-sharded dim); v>1 storage is
    ALREADY [S, v, per, ...] — pass through untouched, so no relayout
    (and no involuntary SPMD rematerialization) ever happens."""
    n_chunks = num_stages * n_virtual
    if n_layers % n_chunks:
        raise ValueError(
            f"num_hidden_layers={n_layers} must divide evenly over "
            f"pp_degree={num_stages} * virtual_pp_degree={n_virtual}")
    if n_virtual > 1:
        for p in params:
            if p.shape[0] != num_stages or p.shape[1] != n_virtual:
                raise ValueError(
                    f"interleaved stacks must be [S={num_stages}, "
                    f"v={n_virtual}, per, ...]; got {p.shape}")
        return list(params)
    per_chunk = n_layers // n_chunks
    return [p.reshape((n_chunks, per_chunk) + p.shape[1:])
            for p in params]


def _llama_pipe_loss_raw(params, x, labels, cos, sin, norm_w, head_w, *,
                         n_heads, n_kv, head_dim, eps, num_stages, n_micro,
                         transpose_head, pp_axis="pp", n_virtual=1,
                         ignore_index=-100, rope_interleaved=False,
                         stash_residuals=True, remat_policy=None):
    """Decoder stack + loss head as one SPMD pipeline program; the loss
    is computed per microbatch on the last stage (raw jax level)."""
    import jax.numpy as jnp

    from ..distributed.auto_parallel import get_mesh
    from ..distributed.pipeline import gpipe_spmd

    pm = get_mesh()
    stage_fn = _pipe_stage_fn(n_heads, n_kv, head_dim, eps,
                              rope_interleaved, remat_policy)
    tail_fn = _pipe_tail_fn(eps, transpose_head, ignore_index)
    b = x.shape[0]
    n_layers = _pipe_n_layers(params[0], n_virtual)

    pp = pm.mesh.shape.get(pp_axis, 1) if pm is not None else 1
    if num_stages is None:
        num_stages = pp
    if pm is None or pp <= 1 or num_stages <= 1:
        # serial fallback never microbatches — no divisibility demands
        h = stage_fn(_pipe_layer_view(params, n_virtual, n_layers),
                     x, cos, sin)
        loss_sum, count = tail_fn((norm_w, head_w), h,
                                  labels)
        return loss_sum / jnp.maximum(count, 1.0)

    if b % n_micro:
        raise ValueError(
            f"batch size {b} must be divisible by n_microbatches={n_micro}")
    xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])
    lm = labels.reshape((n_micro, b // n_micro) + labels.shape[1:])

    stacked = _pipe_chunked(params, num_stages, n_virtual, n_layers)
    # training default: fused 1F1B schedule — interleaved when
    # n_virtual > 1 (activation memory ∝ pp in-flight microbatches,
    # not n_micro); custom_vjp, so this is also the eval path (plain
    # fwd pipeline) when not under grad.  Residual stashing composes
    # with interleaving (per-lap switch branches keep chunk tracers
    # static for the weight-identity filter).
    from ..distributed.pipeline import pipeline_train_1f1b
    return pipeline_train_1f1b(
        stage_fn, tail_fn, pm.mesh, pp_axis, tuple(stacked), xm,
        (cos, sin), (norm_w, head_w), (lm,), stash_residuals,
        n_virtual)


def _llama_pipe_raw(params, x, cos, sin, *, n_heads, n_kv, head_dim, eps,
                    num_stages, n_micro, pp_axis="pp", n_virtual=1,
                    rope_interleaved=False):
    """Decoder stack as an SPMD GPipe/interleaved pipeline (raw jax level).

    params: 9 stacked arrays, each [L, ...] (order of _decoder_layer_raw).
    """
    import jax

    from ..distributed.auto_parallel import get_mesh
    from ..distributed.pipeline import gpipe_spmd

    n_layers = _pipe_n_layers(params[0], n_virtual)
    stage_fn = _pipe_stage_fn(n_heads, n_kv, head_dim, eps,
                              rope_interleaved)

    pm = get_mesh()
    pp = pm.mesh.shape.get(pp_axis, 1) if pm is not None else 1
    if num_stages is None:
        num_stages = pp

    if pm is None or pp <= 1 or num_stages <= 1:
        # no pipeline axis: plain scan over layers (single-chip / dp-only)
        return stage_fn(_pipe_layer_view(params, n_virtual, n_layers),
                        x, cos, sin)

    stacked = _pipe_chunked(params, num_stages, n_virtual, n_layers)
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(
            f"batch size {b} must be divisible by n_microbatches={n_micro}")
    xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    out = gpipe_spmd(stacked, xm, stage_fn, cos, sin,
                     mesh=pm.mesh, pp_axis=pp_axis, n_virtual=n_virtual)
    return out.reshape(x.shape)


class LlamaForCausalLMPipe(Layer):
    """Pipeline-parallel Llama (PaddleNLP LlamaForCausalLMPipe parity).

    Decoder-layer parameters are stacked on a leading layer axis that is
    sharded over the ``pp`` mesh dim (plus the usual Megatron TP specs on
    the trailing dims); embedding / final norm / lm-head run outside the
    pipeline region.  Requires num_hidden_layers % pp_degree == 0.
    """

    def __init__(self, config: LlamaConfig, n_microbatches: int = 4,
                 virtual_pp_degree: int = 1,
                 num_stages: Optional[int] = None):
        super().__init__()
        self.config = config
        self.n_microbatches = n_microbatches
        self.virtual_pp_degree = virtual_pp_degree
        c = config
        hd = c.hidden_size // c.num_attention_heads
        self.head_dim = hd
        init = Normal(0.0, c.initializer_range)
        out_init = Normal(0.0, c.initializer_range /
                          math.sqrt(2 * c.num_hidden_layers))
        L, H = c.num_hidden_layers, c.hidden_size

        v = virtual_pp_degree
        if v > 1:
            # INTERLEAVED storage: device d owns chunks d, d+S, ... so
            # stacks live as [S, v, per_chunk, ...] with pp on dim 0 —
            # the exact per-device layout the engine consumes.  Storing
            # global chunk order [v*S, ...] instead forces an
            # involuntary-full-rematerialization reshard of EVERY stack
            # each step (the [vS]->[S,v] relayout moves weights across
            # pp shards; surfaced by the r4 dryrun's SPMD warnings).
            # S must therefore be known at construction (the reference's
            # interleaved PipelineLayer takes the topology then too).
            if num_stages is None:
                from ..distributed.auto_parallel import get_mesh
                pm = get_mesh()
                from ..common.errors import enforce
                enforce(pm is not None and pm.mesh.shape.get("pp", 1) > 1,
                        "virtual_pp_degree > 1 needs num_stages= or an "
                        "active pp mesh at construction")
                num_stages = int(pm.mesh.shape["pp"])
            from ..common.errors import enforce
            enforce(L % (num_stages * v) == 0,
                    f"num_hidden_layers={L} must divide over "
                    f"pp {num_stages} * virtual_pp_degree {v}")
        self.num_stages = num_stages
        per = L // (num_stages * v) if v > 1 else None

        def stacked(shape, ini, spec):
            if v > 1:
                p = self.create_parameter([num_stages, v, per] + shape,
                                          default_initializer=ini)
                p.dist_spec = ("pp", None, None) + spec
            else:
                p = self.create_parameter([L] + shape,
                                          default_initializer=ini)
                p.dist_spec = ("pp",) + spec
            return p

        self.input_ln = stacked([H], Constant(1.0), (None,))
        self.q_w = stacked([H, c.num_attention_heads * hd], init,
                           (None, "mp"))
        self.k_w = stacked([H, c.num_key_value_heads * hd], init,
                           (None, "mp"))
        self.v_w = stacked([H, c.num_key_value_heads * hd], init,
                           (None, "mp"))
        self.o_w = stacked([c.num_attention_heads * hd, H], out_init,
                           ("mp", None))
        self.post_ln = stacked([H], Constant(1.0), (None,))
        self.gate_w = stacked([H, c.intermediate_size], init, (None, "mp"))
        self.up_w = stacked([H, c.intermediate_size], init, (None, "mp"))
        self.down_w = stacked([c.intermediate_size, H], out_init,
                              ("mp", None))

        self.embed_tokens = Embedding(c.vocab_size, H, weight_attr=init)
        self.embed_tokens.weight.dist_spec = ("mp", None)
        self.norm = RMSNorm(H, epsilon=c.rms_norm_eps)
        if c.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(H, c.vocab_size, bias_attr=False,
                                  weight_attr=init)
            self.lm_head.weight.dist_spec = (None, "mp")
        rope = _rope_cos_sin(c.max_position_embeddings, hd, c.rope_theta)
        self.register_buffer("rope_cos", Tensor(np.cos(rope)),
                             persistable=False)
        self.register_buffer("rope_sin", Tensor(np.sin(rope)),
                             persistable=False)

    def forward(self, input_ids, labels=None):
        c = self.config
        b, s = input_ids.shape
        x = self.embed_tokens(input_ids)
        cos = self.rope_cos[:s]
        sin = self.rope_sin[:s]
        stack = [self.input_ln, self.q_w, self.k_w, self.v_w, self.o_w,
                 self.post_ln, self.gate_w, self.up_w, self.down_w]
        if labels is not None and c.fuse_linear_cross_entropy:
            # training path: loss head fused into the pipeline's last
            # stage (scalar psum instead of whole-batch output gather);
            # fuse_linear_cross_entropy=False falls through to the
            # gather + unfused-criterion path below
            tied = self.lm_head is None
            head_w = (self.embed_tokens.weight if tied
                      else self.lm_head.weight)
            return apply_op(
                _llama_pipe_loss_raw, stack, x, labels, cos, sin,
                self.norm.weight, head_w,
                n_heads=c.num_attention_heads, n_kv=c.num_key_value_heads,
                head_dim=self.head_dim, eps=c.rms_norm_eps,
                num_stages=None, n_micro=self.n_microbatches,
                transpose_head=tied, n_virtual=self.virtual_pp_degree,
                rope_interleaved=getattr(c, "rope_interleaved", False),
                stash_residuals=getattr(c, "pp_stash_residuals", True),
                remat_policy=(c.recompute_granularity if c.recompute
                              else None))
        x = apply_op(
            _llama_pipe_raw, stack, x, cos, sin,
            n_heads=c.num_attention_heads, n_kv=c.num_key_value_heads,
            head_dim=self.head_dim, eps=c.rms_norm_eps,
            num_stages=None, n_micro=self.n_microbatches,
            n_virtual=self.virtual_pp_degree,
            rope_interleaved=getattr(c, "rope_interleaved", False))
        x = self.norm(x)
        if self.lm_head is None:
            logits = P.matmul(x, self.embed_tokens.weight, transpose_y=True)
        else:
            logits = self.lm_head(x)
        if labels is not None:
            return LlamaPretrainingCriterion()(logits, labels)
        return logits


class LlamaPretrainingCriterion(Layer):
    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        return F.cross_entropy(
            P.reshape(logits, [-1, logits.shape[-1]]),
            P.reshape(labels, [-1]),
            ignore_index=self.ignore_index)
