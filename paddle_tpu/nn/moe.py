"""Mixture-of-Experts layers with expert parallelism.

Reference parity: python/paddle/incubate/distributed/models/moe/
(MoELayer, gate/ top-k gates with aux load-balance losses) plus the
phi/kernels/fusion moe dispatch kernels (SURVEY.md §2.3 EP row).

TPU-native design, two dispatch paths behind one layer:

- **dense** (GShard/Switch): routing produces dispatch/combine tensors
  and the token→expert shuffle is two einsums that the XLA SPMD
  partitioner lowers to all-to-alls over the expert axes; expert FFNs
  are ONE batched matmul over stacked [E, ...] weights sharded on the
  ``ep``/(dp, sharding) expert axes.  This is the multi-chip path — the
  reference's MoE alltoall runtime collapses into sharding annotations.
- **grouped** (dropless, megablox-class): tokens are sorted by expert
  into a tile-aligned buffer and the expert FFN runs as Pallas grouped
  matmuls (ops/pallas/grouped_matmul.py) — no [T, E, C] capacity
  padding, no dropped tokens, every MXU cycle does useful work.  This
  is the single-chip / per-shard fast path (the reference's fused phi
  MoE kernels analog).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import device as _device
from ..tensor import Tensor, apply_op
from .initializer import Normal
from .layer import Layer

__all__ = ["TopKGate", "ExpertFFN", "MoELayer", "moe_dispatch_combine"]

# expert dim shards over the dedicated ep axis, then folds over the data
# axes (DeepSpeed-MoE EP=DP folding) for any remaining factor
EP_AXES = ("ep", "dp", "sharding")


def _router_parts(x, wg, *, k, norm_topk=True):
    """Router math split into combinable parts: x [T,H], wg [H,E] ->
    gate_vals [T,k] (f32), expert_idx [T,k] (int32), plus the per-token
    MEANS the aux loss is assembled from (density [E], density_proxy
    [E], zsq scalar).  Means over equal-size token shards average to the
    global mean, so the EP path reconstructs the exact global aux with a
    ``pmean`` over the expert fold.  ``norm_topk`` renormalises the
    top-k gate values (Mixtral convention; HF Qwen2-MoE ships
    norm_topk_prob=False)."""
    e = wg.shape[1]
    logits = jnp.dot(x.astype(jnp.float32), wg.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]

    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # [T, k]
    if norm_topk:
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # aux load-balance parts over the FULL top-k assignment density (the
    # reference's top-k gates count every selected slot, not just slot 0 —
    # ADVICE.md round-1): fraction of routed slots landing on each expert
    topk_onehot = jax.nn.one_hot(expert_idx, e)              # [T, k, E]
    density = jnp.mean(jnp.sum(topk_onehot, axis=1), axis=0) / k
    density_proxy = jnp.mean(probs, axis=0)
    zsq = jnp.mean(jnp.square(
        jax.scipy.special.logsumexp(logits, axis=-1)))
    return gate_vals, expert_idx, density, density_proxy, zsq


def _assemble_aux(density, density_proxy, zsq, *, balance_coef, z_coef):
    e = density.shape[0]
    aux = balance_coef * e * jnp.sum(density * density_proxy)
    if z_coef:
        aux = aux + z_coef * zsq
    return aux


def _router_topk(x, wg, *, k, balance_coef, z_coef, norm_topk=True):
    """Shared router math: x [T,H], wg [H,E] -> gate_vals [T,k] (f32),
    expert_idx [T,k] (int32), aux_loss (scalar)."""
    gate_vals, expert_idx, density, proxy, zsq = _router_parts(
        x, wg, k=k, norm_topk=norm_topk)
    aux = _assemble_aux(density, proxy, zsq, balance_coef=balance_coef,
                        z_coef=z_coef)
    return gate_vals, expert_idx, aux


def _gate_raw(x, wg, *, k, capacity, balance_coef, z_coef,
              norm_topk=True):
    """Router: x [T,H], wg [H,E] -> combine [T,E,C], dispatch [T,E,C],
    aux_loss (scalar).  Switch-style load-balance + router z-loss."""
    t = x.shape[0]
    e = wg.shape[1]
    gate_vals, expert_idx, aux = _router_topk(
        x, wg, k=k, balance_coef=balance_coef, z_coef=z_coef,
        norm_topk=norm_topk)

    # capacity positions: for each (slot, expert) the position within the
    # expert's buffer = number of earlier tokens routed to it
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [T, k, E]
    flat = onehot.reshape(t * k, e)  # slot-major: token t slot j -> t*k+j
    pos = jnp.cumsum(flat, axis=0) - flat                    # [T*k, E]
    pos = pos.reshape(t, k, e)
    in_cap = (pos < capacity) & (onehot > 0)                 # [T, k, E]

    pos_c = jax.nn.one_hot(jnp.where(in_cap, pos, capacity),
                           capacity + 1, dtype=jnp.float32)[..., :capacity]
    # dispatch/combine [T, E, C]
    dispatch = jnp.einsum("tke,tkec->tec",
                          onehot.astype(jnp.float32) *
                          in_cap.astype(jnp.float32), pos_c)
    combine = jnp.einsum("tk,tke,tkec->tec", gate_vals.astype(jnp.float32),
                         onehot.astype(jnp.float32) *
                         in_cap.astype(jnp.float32), pos_c)
    return combine, dispatch, aux


def moe_dispatch_combine(x, combine, dispatch, expert_fn):
    """Route tokens through ``expert_fn`` with the gate's dispatch and
    combine tensors: x [T,H] -> [T,H].  The two einsums are what GSPMD
    lowers to all-to-alls when T and E are sharded on different axes."""
    xe = apply_op(_dispatch_raw, x, dispatch)
    eo = expert_fn(xe)
    return apply_op(_combine_raw, eo, combine)


def _moe_grouped_raw(x, router_w, gate_w, up_w, down_w, *, k,
                     balance_coef, z_coef, tm, interpret,
                     norm_topk=True):
    """Fused dropless MoE forward: router + sorted tile-aligned dispatch
    + Pallas grouped-matmul SwiGLU experts + top-k combine, all inside
    one raw fn so the integer routing tensors never surface as framework
    Tensors.  Returns (out [T,H], aux_loss)."""
    from ..ops.pallas.grouped_matmul import dropless_moe_ffn
    gate_vals, expert_idx, aux = _router_topk(
        x, router_w, k=k, balance_coef=balance_coef, z_coef=z_coef,
        norm_topk=norm_topk)
    out = dropless_moe_ffn(x, gate_vals, expert_idx, gate_w, up_w,
                           down_w, tm=tm, interpret=interpret)
    return out, aux


class TopKGate(Layer):
    """Top-k router (paddle incubate moe gate family parity)."""

    def __init__(self, hidden_size: int, num_experts: int, k: int = 2,
                 capacity_factor: float = 1.25,
                 balance_loss_weight: float = 0.01,
                 z_loss_weight: float = 0.0, norm_topk_prob: bool = True):
        super().__init__()
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.balance_loss_weight = balance_loss_weight
        self.z_loss_weight = z_loss_weight
        self.norm_topk_prob = norm_topk_prob
        self.weight = self.create_parameter(
            [hidden_size, num_experts],
            default_initializer=Normal(0.0, 0.02))

    def capacity(self, num_tokens: int) -> int:
        cap = int(math.ceil(
            self.k * num_tokens * self.capacity_factor / self.num_experts))
        return max(cap, 4)

    def forward(self, x) -> Tuple[Tensor, Tensor, Tensor]:
        cap = self.capacity(int(np.prod(x.shape[:-1])))
        return apply_op(_gate_raw, x, self.weight, k=self.k, capacity=cap,
                        balance_coef=self.balance_loss_weight,
                        z_coef=self.z_loss_weight,
                        norm_topk=self.norm_topk_prob)


def _expert_ffn_raw(xe, wg, wu, wd):
    """Batched SwiGLU over experts: xe [E,C,H]; w* [E,H,F]/[E,F,H]."""
    h = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, wg))
    h = h * jnp.einsum("ech,ehf->ecf", xe, wu)
    return jnp.einsum("ecf,efh->ech", h, wd)


class ExpertFFN(Layer):
    """Stacked per-expert SwiGLU FFN — one batched matmul on the MXU,
    expert dim sharded over the EP fold."""

    def __init__(self, num_experts: int, hidden_size: int,
                 intermediate_size: int, init_std: float = 0.02,
                 num_layers_scale: int = 1):
        super().__init__()
        init = Normal(0.0, init_std)
        out_init = Normal(0.0, init_std / math.sqrt(2 * num_layers_scale))

        def param(shape, ini, spec):
            p = self.create_parameter(shape, default_initializer=ini)
            p.dist_spec = spec
            return p

        e, h, f = num_experts, hidden_size, intermediate_size
        self.gate_w = param([e, h, f], init, (EP_AXES, None, "mp"))
        self.up_w = param([e, h, f], init, (EP_AXES, None, "mp"))
        self.down_w = param([e, f, h], out_init, (EP_AXES, "mp", None))

    def forward(self, xe):
        return apply_op(_expert_ffn_raw, xe, self.gate_w, self.up_w,
                        self.down_w)


def _dispatch_raw(x, dispatch):
    return jnp.einsum("tec,th->ech", dispatch, x.astype(jnp.float32)
                      ).astype(x.dtype)


def _combine_raw(expert_out, combine):
    return jnp.einsum("ech,tec->th", expert_out.astype(jnp.float32),
                      combine).astype(expert_out.dtype)


class MoELayer(Layer):
    """paddle.incubate.distributed.models.moe.MoELayer parity.

    forward(x [B,S,H]) -> [B,S,H]; the router's aux loss for the step is
    exposed as ``self.aux_loss`` (models sum it into the train loss, the
    reference's pattern).

    ``ep_capacity_factor`` bounds the grouped_ep path's TOTAL per-shard
    receive buffer at factor × the balanced load (``None`` = strictly
    dropless at any router skew); the ragged exchange itself always
    moves exactly the routed rows.  Set ``FLAGS_moe_log_drops=1`` to
    print the exact dropped-row count per call (device-side
    ``jax.debug.print``, works under jit) — the observable twin of the
    reference's capacity/overflow logging.
    """

    def __init__(self, hidden_size: int, num_experts: int,
                 intermediate_size: int, k: int = 2,
                 capacity_factor: float = 1.25,
                 shared_expert_intermediate: int = 0,
                 balance_loss_weight: float = 0.01,
                 init_std: float = 0.02, num_layers_scale: int = 1,
                 gate: Optional[TopKGate] = None, experts=None,
                 dispatch_mode: str = "auto",
                 group_tile: Optional[int] = None,
                 norm_topk_prob: bool = True,
                 use_shared_expert_gate: bool = False,
                 ep_capacity_factor: Optional[float] = 2.0):
        super().__init__()
        from ..common.errors import enforce
        enforce(dispatch_mode in ("auto", "dense", "grouped",
                                  "grouped_ep"),
                f"bad dispatch_mode {dispatch_mode!r}")
        self.dispatch_mode = dispatch_mode
        self.group_tile = group_tile
        self.ep_capacity_factor = ep_capacity_factor
        self.gate = gate or TopKGate(
            hidden_size, num_experts, k=k, capacity_factor=capacity_factor,
            balance_loss_weight=balance_loss_weight,
            norm_topk_prob=norm_topk_prob)
        self.experts = experts or ExpertFFN(
            num_experts, hidden_size, intermediate_size, init_std=init_std,
            num_layers_scale=num_layers_scale)
        if shared_expert_intermediate:
            from .common import Linear
            self.shared_gate = Linear(hidden_size,
                                      shared_expert_intermediate,
                                      bias_attr=False)
            self.shared_up = Linear(hidden_size,
                                    shared_expert_intermediate,
                                    bias_attr=False)
            self.shared_down = Linear(shared_expert_intermediate,
                                      hidden_size, bias_attr=False)
            self.shared_gate.weight.dist_spec = (None, "mp")
            self.shared_up.weight.dist_spec = (None, "mp")
            self.shared_down.weight.dist_spec = ("mp", None)
            # HF Qwen2-MoE gates the shared expert with sigmoid(x @ W1)
            if use_shared_expert_gate:
                from .common import Linear
                self.shared_expert_gate = Linear(hidden_size, 1,
                                                 bias_attr=False)
            else:
                self.shared_expert_gate = None
        else:
            self.shared_gate = None
            self.shared_expert_gate = None
        self.aux_loss: Optional[Tensor] = None

    def _resolve_dispatch(self, num_tokens: int) -> str:
        """'grouped' (dropless Pallas) on a single chip / unsharded
        experts on TPU; 'grouped_ep' (shard_map all-to-all + per-shard
        grouped matmul) when the expert fold is active on TPU; 'dense'
        (GShard einsums → GSPMD all-to-alls) off-TPU or when shapes
        don't divide the fold.  Resolved at trace time — mesh state and
        backend are static then."""
        mode = self.dispatch_mode
        custom = not (isinstance(self.gate, TopKGate)
                      and isinstance(self.experts, ExpertFFN))
        if mode == "auto" and custom:
            return "dense"
        from ..distributed.auto_parallel import get_mesh
        pm = get_mesh()
        fold = 1
        divisible = False
        if pm is not None:
            from ..distributed.expert_parallel import (
                ep_grouped_compatible, expert_fold_axes)
            fold = int(np.prod([pm.mesh.shape[a]
                                for a in expert_fold_axes(pm.mesh)],
                               dtype=np.int64))
            divisible = ep_grouped_compatible(
                pm.mesh, self.gate.num_experts, num_tokens)
        if mode == "grouped_ep" or (mode == "auto" and fold > 1):
            if mode == "grouped_ep":
                from ..common.errors import enforce
                enforce(divisible,
                        f"grouped_ep needs experts "
                        f"({self.gate.num_experts}) and tokens "
                        f"({num_tokens}) divisible by the expert fold "
                        f"({fold})")
                return "grouped_ep"
            if divisible and _device.is_compiled_with_tpu():
                return "grouped_ep"
            return "dense"
        if mode != "auto":
            return mode
        # mp-only sharding (no expert fold): the F dim is tensor-sharded
        # — keep the GSPMD-partitionable einsums
        if pm is not None and pm.mesh.shape.get("mp", 1) > 1:
            return "dense"
        return "grouped" if _device.is_compiled_with_tpu() else "dense"

    def forward(self, x):
        b, s, h = x.shape
        flat = apply_op(lambda a: a.reshape(b * s, h), x)
        mode = self._resolve_dispatch(b * s)
        if mode == "grouped_ep":
            from ..common.flags import get_flags
            from ..distributed.auto_parallel import get_mesh
            from ..distributed.expert_parallel import moe_grouped_ep_raw
            log_drops = bool(get_flags("moe_log_drops")["moe_log_drops"])
            out, aux, dropped = apply_op(
                moe_grouped_ep_raw, flat, self.gate.weight,
                self.experts.gate_w, self.experts.up_w,
                self.experts.down_w, k=self.gate.k,
                balance_coef=self.gate.balance_loss_weight,
                z_coef=self.gate.z_loss_weight, tm=self.group_tile,
                interpret=not _device.is_compiled_with_tpu(),
                norm_topk=self.gate.norm_topk_prob,
                mesh=get_mesh().mesh,
                capacity_factor=self.ep_capacity_factor,
                return_drops=True)
            if log_drops:
                jax.debug.print(
                    "moe_grouped_ep dropped {d} / {t} routed rows "
                    "(ep_capacity_factor={f})",
                    d=getattr(dropped, "value", dropped),
                    t=b * s * self.gate.k, f=self.ep_capacity_factor)
        elif mode == "grouped":
            out, aux = apply_op(
                _moe_grouped_raw, flat, self.gate.weight,
                self.experts.gate_w, self.experts.up_w,
                self.experts.down_w, k=self.gate.k,
                balance_coef=self.gate.balance_loss_weight,
                z_coef=self.gate.z_loss_weight, tm=self.group_tile,
                interpret=not _device.is_compiled_with_tpu(),
                norm_topk=self.gate.norm_topk_prob)
        else:
            combine, dispatch, aux = self.gate(flat)
            out = moe_dispatch_combine(flat, combine, dispatch,
                                       self.experts)
        self.aux_loss = aux
        if self.shared_gate is not None:
            from . import functional as F_
            shared = self.shared_down(
                F_.silu(self.shared_gate(flat)) * self.shared_up(flat))
            if self.shared_expert_gate is not None:
                shared = shared * F_.sigmoid(
                    self.shared_expert_gate(flat))
            out = out + shared
        return apply_op(lambda a: a.reshape(b, s, h), out)
