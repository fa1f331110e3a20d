"""Model-backbone adapter seam for :class:`~.engine.LLMEngine`.

The engine used to read ``model.llama.*`` attributes directly, so any
model that was not literally a ``LlamaForCausalLM`` died with a bare
``AttributeError`` deep inside ``__init__``.  This module is the
reviewable seam that replaced those hardwired reads: a
:class:`BackboneSpec` names everything the serving programs consume —
the decoder layer list, the final norm, the embedding/head weights, the
rope buffers, and (for MoE families) the router geometry — and a small
predicate registry resolves a model instance to its spec by DUCK
TYPING, never by class identity, so converted/quantized wrappers keep
working as long as the attribute shape survives.

Four backbones register here:

- ``llama`` — ``LlamaForCausalLM``-shaped models (``model.llama.*``),
  the original engine contract, byte-identical programs.
- ``qwen2_moe`` — ``Qwen2MoeForCausalLM``/DeepSeekMoE-shaped models
  (top-level ``layers`` whose ``mlp`` is a shared-expert MoE layer).
  The spec additionally carries the router geometry the engine folds
  into its static MoE arch (see inference/moe_dispatch.py).

- ``qwen3_next`` — ``Qwen3NextForCausalLM``-shaped hybrids: decoder
  layers of two KINDS in a fixed period (``linear``: a Gated-DeltaNet
  mixer with a per-slot recurrent state; ``full``: gated softmax
  attention over KV pages), each followed by an expert layer that may
  hold a share of the published experts.  The spec carries the
  per-layer kind and the held expert range; the engine admits it
  through ``begin_request`` only and REFUSES at construction what it
  does not carry for layers of several kinds: prefix caching, a tp
  ``mesh=``, a ``draft_model=``, int8 KV or weights, capacity-factor
  dispatch.

- ``nemotron_h`` — ``NemotronHForCausalLM``-shaped hybrids: blocks
  that are a mixer ALONE (``ssm``: a Mamba-2 mixer with a per-slot
  recurrent state; ``full``: softmax attention over KV pages with no
  position signal) or a feed-forward part alone (``ffn``: a latent
  expert layer with a sigmoid router that may hold a share of the
  published experts), one norm a block.  Admitted and refused as the
  other hybrid is.

Unsupported models get ONE clear error listing what would make them
servable, instead of the old attribute crash.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, NamedTuple, Optional

from ..common.errors import enforce

__all__ = ["BackboneSpec", "HybridArch", "register_backbone",
           "resolve_backbone"]


class HybridArch(NamedTuple):
    """Hashable static-jit description of a backbone whose layers are
    of several kinds.  ``kinds`` names each layer's MIXER: ``"full"``
    (softmax attention over the KV pages), one of the two recurrent
    mixers with a per-slot state — ``"linear"`` (Gated DeltaNet) or
    ``"ssm"`` (Mamba-2 / SSD) — or ``"ffn"``: no mixer, the layer is
    its feed-forward part alone.  (Whether a layer HAS a feed-forward
    part, and of which form, is read from its weights.)  The recurrent
    mixer's geometry is kept under its config's own names
    (``ops/pallas/gated_delta.py``'s and ``mamba2_ssd.py``'s helpers
    read either a config or this); the fields of the mixer a backbone
    has not are 0.  ``rotary_dim`` 0 means no rotary at all;
    ``zero_centred_norm``: the norms' stored weights are zero-centred
    (scale ``1 + w``, the product taken in float32)."""
    kinds: tuple
    rotary_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    conv_channels: int
    zero_centred_norm: bool
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 0
    ssm_state_size: int = 0

    @property
    def n_linear(self) -> int:
        """Layers with a recurrent mixer, of either recurrence."""
        return sum(k in ("linear", "ssm") for k in self.kinds)

    @property
    def n_full(self) -> int:
        return sum(k == "full" for k in self.kinds)

    @property
    def state_shape(self) -> tuple:
        """One slot's float32 recurrent state in one layer."""
        if self.mamba_num_heads:
            return (self.mamba_num_heads, self.mamba_head_dim,
                    self.ssm_state_size)
        return (self.linear_num_value_heads, self.linear_key_head_dim,
                self.linear_value_head_dim)


@dataclass
class BackboneSpec:
    """Everything LLMEngine reads off a model, named once.

    ``moe`` is ``None`` for dense-FFN backbones; for MoE backbones it
    is the router geometry dict (num_experts, top_k, norm_topk,
    capacity_factor, shared, shared_gate) the engine freezes into its
    static dispatch arch and its capsule fingerprint (plus
    ``expert_lo`` / ``experts_held`` when the layer holds a share).
    ``hybrid`` is ``None`` for backbones whose layers are all of one
    kind; with it comes ``layer_weights``, one weight dict a layer whose
    leaves are the model's own arrays (the engine's layer loop uses them
    where they lie instead of stacking a second copy)."""
    arch: str
    config: Any
    layers: List[Any]
    norm: Any
    embed_tokens: Any
    lm_head: Optional[Any]
    rope_cos: Any
    rope_sin: Any
    attn_bias: bool = False
    moe: Optional[dict] = None
    hybrid: Optional[HybridArch] = None
    layer_weights: Optional[tuple] = None


# ordered (arch, predicate, builder) triples — first predicate match
# wins, so register more specific shapes before more general ones
_REGISTRY: List[tuple] = []


def register_backbone(arch: str, predicate: Callable[[Any], bool],
                      builder: Callable[[Any], "BackboneSpec"]):
    """Register a servable model family: ``predicate(model)`` decides
    membership by duck typing, ``builder(model)`` produces the spec.
    Later registrations of the same ``arch`` replace the earlier one
    (tests swap in instrumented builders)."""
    global _REGISTRY
    _REGISTRY = [(a, p, b) for (a, p, b) in _REGISTRY if a != arch]
    _REGISTRY.append((arch, predicate, builder))


def resolve_backbone(model) -> BackboneSpec:
    """Resolve ``model`` to its BackboneSpec, or raise ONE clear error
    naming the supported families."""
    for arch, pred, build in _REGISTRY:
        try:
            matched = bool(pred(model))
        except Exception:
            matched = False
        if matched:
            return build(model)
    supported = ", ".join(a for a, _, _ in _REGISTRY)
    raise ValueError(
        f"LLMEngine cannot serve {type(model).__name__}: no registered "
        f"backbone matches it (supported: {supported}).  A servable "
        f"model exposes either a ``.llama`` submodule (Llama family) "
        f"or top-level ``layers``/``norm``/``embed_tokens``/``rope_*`` "
        f"with a shared-expert MoE ``mlp`` (Qwen2-MoE/DeepSeekMoE "
        f"family), or the same with per-layer ``kind`` and "
        f"``linear_attn``/``self_attn`` mixers (Qwen3-Next hybrid "
        f"family: unified step only — no prefix caching, split "
        f"programs, tp mesh, draft model, int8 or capacity-factor "
        f"dispatch), or per-block ``kind`` (``ssm`` / ``full`` / "
        f"``ffn``) with ONE part a block under ``mixer`` (Nemotron-H "
        f"hybrid family: the same refusals); register new families with "
        f"inference.backbone.register_backbone().")


# -- llama ------------------------------------------------------------------

def _is_llama(model) -> bool:
    return hasattr(model, "llama") and hasattr(model.llama, "layers")


def _build_llama(model) -> BackboneSpec:
    lm = model.llama
    layers = list(lm.layers)
    enforce(layers, "model.llama.layers is empty")
    # the dense serving programs carry no qkv bias arrays; a biased
    # Llama checkpoint would silently drop its biases (wrong tokens),
    # so refuse it loudly — the Qwen2-MoE path is the biased one
    enforce(layers[0].self_attn.q_proj.bias is None,
            "Llama backbone with attention biases is not servable by "
            "the dense engine path (the stacked programs carry no "
            "bias arrays); biased attention serves via the MoE "
            "backbone family")
    return BackboneSpec(
        arch="llama", config=model.config, layers=layers,
        norm=lm.norm, embed_tokens=lm.embed_tokens,
        lm_head=model.lm_head, rope_cos=lm.rope_cos,
        rope_sin=lm.rope_sin, attn_bias=False, moe=None)


# -- qwen2-moe / deepseek-moe ----------------------------------------------

def _is_qwen2_moe(model) -> bool:
    if hasattr(model, "llama") or not hasattr(model, "layers"):
        return False
    layers = list(model.layers)
    if not layers:
        return False
    mlp = getattr(layers[0], "mlp", None)
    gate = getattr(mlp, "gate", None)
    return (hasattr(model, "norm") and hasattr(model, "embed_tokens")
            and hasattr(model, "rope_cos")
            and hasattr(mlp, "experts")
            and hasattr(gate, "num_experts") and hasattr(gate, "k"))


def _build_qwen2_moe(model) -> BackboneSpec:
    layers = list(model.layers)
    g0, m0 = layers[0].mlp.gate, layers[0].mlp
    for l in layers[1:]:
        g, m = l.mlp.gate, l.mlp
        enforce(g.num_experts == g0.num_experts and g.k == g0.k
                and g.norm_topk_prob == g0.norm_topk_prob
                and (m.shared_gate is None) == (m0.shared_gate is None)
                and (m.shared_expert_gate is None)
                == (m0.shared_expert_gate is None),
                "MoE serving needs one router/shared-expert geometry "
                "across all decoder layers (the dispatch arch is one "
                "static jit argument)")
    attn_bias = layers[0].self_attn.q_proj.bias is not None
    return BackboneSpec(
        arch="qwen2_moe", config=model.config, layers=layers,
        norm=model.norm, embed_tokens=model.embed_tokens,
        lm_head=model.lm_head, rope_cos=model.rope_cos,
        rope_sin=model.rope_sin, attn_bias=attn_bias,
        moe={"num_experts": int(g0.num_experts), "top_k": int(g0.k),
             "norm_topk": bool(g0.norm_topk_prob),
             "capacity_factor": float(g0.capacity_factor),
             "shared": m0.shared_gate is not None,
             "shared_gate": m0.shared_expert_gate is not None})


# -- qwen3-next hybrid (linear + full layers, an expert share) -----------------

def _is_qwen3_next(model) -> bool:
    if hasattr(model, "llama") or not hasattr(model, "layers"):
        return False
    layers = list(model.layers)
    return bool(layers) and all(
        getattr(l, "kind", None) in ("linear", "full")
        and hasattr(l, "linear_attn" if l.kind == "linear"
                    else "self_attn")
        and hasattr(getattr(l, "mlp", None), "experts")
        for l in layers) and hasattr(model, "serving_layer_weights")


def _build_qwen3_next(model) -> BackboneSpec:
    c = model.config
    layers = list(model.layers)
    g0 = layers[0].mlp.gate
    lo, n_held = c.held
    return BackboneSpec(
        arch="qwen3_next", config=c, layers=layers, norm=model.norm,
        embed_tokens=model.embed_tokens, lm_head=model.lm_head,
        rope_cos=model.rope_cos, rope_sin=model.rope_sin,
        attn_bias=False,
        moe={"num_experts": int(g0.num_experts), "top_k": int(g0.k),
             "norm_topk": bool(g0.norm_topk_prob),
             "capacity_factor": float(g0.capacity_factor),
             "shared": True, "shared_gate": True,
             "expert_lo": int(lo), "experts_held": int(n_held)},
        hybrid=HybridArch(
            kinds=tuple(l.kind for l in layers),
            rotary_dim=int(c.rotary_dim),
            linear_num_key_heads=int(c.linear_num_key_heads),
            linear_num_value_heads=int(c.linear_num_value_heads),
            linear_key_head_dim=int(c.linear_key_head_dim),
            linear_value_head_dim=int(c.linear_value_head_dim),
            linear_conv_kernel_dim=int(c.linear_conv_kernel_dim),
            conv_channels=int(c.conv_channels),
            zero_centred_norm=True),
        layer_weights=model.serving_layer_weights())


# -- nemotron-h hybrid (a mixer or an expert layer a block) ---------------------

def _is_nemotron_h(model) -> bool:
    if hasattr(model, "llama") or not hasattr(model, "layers"):
        return False
    layers = list(model.layers)
    return bool(layers) and all(
        getattr(l, "kind", None) in ("ssm", "full", "ffn")
        and hasattr(l, "mixer") and hasattr(l, "norm")
        for l in layers) and hasattr(model, "serving_layer_weights")


def _build_nemotron_h(model) -> BackboneSpec:
    c = model.config
    layers = list(model.layers)
    arch = model.moe_arch()
    moe = {"num_experts": arch.num_experts, "top_k": arch.top_k,
           "norm_topk": arch.norm_topk, "capacity_factor": 1.0,
           "shared": arch.shared, "shared_gate": arch.shared_gate,
           "expert_lo": arch.expert_lo, "experts_held": arch.n_held,
           "scoring": arch.scoring, "route_scale": arch.route_scale,
           "expert_act": arch.expert_act}
    return BackboneSpec(
        arch="nemotron_h", config=c, layers=layers, norm=model.norm,
        embed_tokens=model.embed_tokens, lm_head=model.lm_head,
        rope_cos=model.rope_cos, rope_sin=model.rope_sin,
        attn_bias=False,
        moe=moe if any(l.kind == "ffn" for l in layers) else None,
        hybrid=HybridArch(
            kinds=tuple(l.kind for l in layers), rotary_dim=0,
            linear_num_key_heads=0, linear_num_value_heads=0,
            linear_key_head_dim=0, linear_value_head_dim=0,
            linear_conv_kernel_dim=int(c.conv_kernel),
            conv_channels=int(c.conv_channels),
            zero_centred_norm=False,
            mamba_num_heads=int(c.mamba_num_heads),
            mamba_head_dim=int(c.mamba_head_dim),
            n_groups=int(c.n_groups),
            ssm_state_size=int(c.ssm_state_size)),
        layer_weights=model.serving_layer_weights())


register_backbone("llama", _is_llama, _build_llama)
# the more specific shapes first: a hybrid also has ``layers``
register_backbone("nemotron_h", _is_nemotron_h, _build_nemotron_h)
register_backbone("qwen3_next", _is_qwen3_next, _build_qwen3_next)
register_backbone("qwen2_moe", _is_qwen2_moe, _build_qwen2_moe)
