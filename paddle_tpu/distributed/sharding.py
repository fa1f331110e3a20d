"""Sharding (ZeRO 1-2-3) planning.

Reference parity: fleet/meta_parallel/sharding/group_sharded_stage{1,2,3}
+ group_sharded_optimizer_stage2 (param/grad/optimizer-state sharding with
allgather-on-demand and reduce-scatter hooks).

TPU-native design (SURVEY.md §2.3): stages become STATIC sharding specs —
  stage 1/2: params replicated over the ``sharding`` axis, optimizer
             moments sharded (grad reduce-scatter is what the partitioner
             emits for sharded-moment updates — stage-2 behavior falls
             out of XLA's scheduling);
  stage 3:   params themselves sharded over ``sharding`` (FSDP); XLA
             inserts the allgather-before-use / discard-after (and
             overlaps them), replacing GroupShardedStage3's python hooks.
The planner combines these with TP specs carried by ``dist_spec`` on
parameters (parallel_layers.py).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ShardingPlan", "plan_param_spec", "group_sharded_parallel",
           "TPShardings"]


class TPShardings:
    """Hashable tensor-parallel sharding plan for the serving engine.

    Carried as a STATIC jit argument by the serving programs
    (engine.py): one distinct ``TPShardings`` per mesh shape hashes to
    one trace, so the one-compile-per-program invariant becomes
    one-compile-per-mesh-shape.  ``Mesh`` itself is hashable, which is
    what makes this safe to put in ``static_argnames``.

    ``constrain(x, dim)`` applies ``with_sharding_constraint`` with the
    tp axis on ``dim`` (``None`` = fully replicated); ``put(x, dim)``
    commits a host array the same way at init time.
    """

    __slots__ = ("mesh", "axis")

    def __init__(self, mesh: Mesh, axis: str = "tp"):
        self.mesh = mesh
        self.axis = axis

    @property
    def tp(self) -> int:
        return _axis_size(self.mesh, self.axis)

    def _sharding(self, ndim: int, dim: Optional[int]):
        spec = [None] * ndim
        if dim is not None:
            spec[dim] = self.axis
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def constrain(self, x, dim: Optional[int] = None):
        return jax.lax.with_sharding_constraint(
            x, self._sharding(x.ndim, dim))

    def per_shard(self, fn, in_dims, out_dims):
        """``fn`` run once per tp shard (``jax.shard_map``, manual over
        the tp axis) — how a Pallas kernel runs under the mesh: Mosaic
        kernels cannot be partitioned by GSPMD, so each shard gets its
        own heads.  ``in_dims`` / ``out_dims`` give, per argument /
        result of ``fn`` (which returns a tuple), the dimension sharded
        over tp (``None`` = replicated)."""
        def spec(dim):
            return PartitionSpec() if dim is None else \
                PartitionSpec(*([None] * dim + [self.axis]))
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=tuple(map(spec, in_dims)),
            out_specs=tuple(map(spec, out_dims)), check_vma=False)

    def put(self, x, dim: Optional[int] = None):
        x = jax.numpy.asarray(x)
        return jax.device_put(x, self._sharding(x.ndim, dim))

    def __hash__(self):
        return hash((self.mesh, self.axis))

    def __eq__(self, other):
        return (isinstance(other, TPShardings)
                and self.mesh == other.mesh and self.axis == other.axis)

    def __repr__(self):
        return f"TPShardings(tp={self.tp}, axis={self.axis!r})"


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def _shardable_dim(shape: Tuple[int, ...], size: int,
                   taken: Tuple[Optional[object], ...]) -> Optional[int]:
    """Largest dim divisible by ``size`` that is not already sharded."""
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if taken[i] is None and shape[i] % size == 0 and shape[i] >= size:
            return i
    return None


def plan_param_spec(param, mesh: Mesh, stage: int,
                    fsdp_axis: str = "sharding") -> PartitionSpec:
    """Combine the param's TP ``dist_spec`` with the ZeRO stage policy."""
    base = list(getattr(param, "dist_spec", None) or
                (None,) * param.ndim)
    base += [None] * (param.ndim - len(base))
    # drop annotated axes the dim cannot divide over (e.g. 4 experts on
    # an 8-wide ep fold) — replicate instead of failing at device_put
    for i, entry in enumerate(base):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        size = 1
        for a in axes:
            if a not in mesh.axis_names:   # e.g. ep on a non-MoE mesh
                continue
            a_sz = _axis_size(mesh, a)
            if param.shape[i] % (size * a_sz) == 0:
                keep.append(a)
                size *= a_sz
        base[i] = tuple(keep) if len(keep) > 1 else (
            keep[0] if keep else None)
    if stage >= 3 and _axis_size(mesh, fsdp_axis) > 1 \
            and fsdp_axis not in jax.tree_util.tree_leaves(base):
        shape = tuple(param.shape)
        dim = _shardable_dim(shape, _axis_size(mesh, fsdp_axis), tuple(base))
        if dim is not None:
            base[dim] = (base[dim], fsdp_axis) if base[dim] is not None \
                else fsdp_axis
    return PartitionSpec(*base)


def _slot_spec(param_spec: PartitionSpec, param_shape, mesh: Mesh,
               stage: int, fsdp_axis: str = "sharding") -> PartitionSpec:
    """Optimizer-moment sharding: same as the param, plus (stage 1/2) the
    sharding axis even when the param is replicated."""
    base = list(param_spec) + [None] * (len(param_shape) - len(param_spec))
    if stage >= 1 and _axis_size(mesh, fsdp_axis) > 1 \
            and fsdp_axis not in jax.tree_util.tree_leaves(base):
        dim = _shardable_dim(tuple(param_shape),
                             _axis_size(mesh, fsdp_axis), tuple(base))
        if dim is not None:
            base[dim] = (base[dim], fsdp_axis) if base[dim] is not None \
                else fsdp_axis
    return PartitionSpec(*base)


class ShardingPlan:
    """Computes NamedShardings for the full train state of a model."""

    def __init__(self, model, mesh: Mesh, stage: int = 1,
                 fsdp_axis: str = "sharding",
                 data_axes: Tuple[str, ...] = ("dp", "sharding")):
        self.model = model
        self.mesh = mesh
        self.stage = stage
        self.fsdp_axis = fsdp_axis
        self.data_axes = data_axes
        self.param_specs: Dict[str, PartitionSpec] = {}
        self.slot_specs: Dict[str, PartitionSpec] = {}
        for name, p in model.named_parameters():
            spec = plan_param_spec(p, mesh, stage, fsdp_axis)
            self.param_specs[name] = spec
            self.slot_specs[name] = _slot_spec(spec, p.shape, mesh, stage,
                                               fsdp_axis)

    # -- shardings for the CompiledTrainStep state pytree -------------------
    def state_shardings(self, state):
        mesh = self.mesh

        def param_shard(name):
            return NamedSharding(mesh, self.param_specs[name])

        params_s = {k: param_shard(k) for k in state["params"]}
        slots_s = {}
        for k, slots in state["opt"]["slots"].items():
            spec = self.slot_specs.get(k, PartitionSpec())
            slots_s[k] = {s: NamedSharding(mesh, spec) for s in slots}
        return {"params": params_s,
                "opt": {"slots": slots_s,
                        "step": NamedSharding(mesh, PartitionSpec())}}

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        """Global batch sharded over the data axes on dim 0."""
        axes = tuple(a for a in self.data_axes
                     if _axis_size(self.mesh, a) > 1)
        spec = PartitionSpec(axes if axes else None,
                             *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def place_state(self, state):
        """device_put the whole state tree onto the mesh per plan."""
        sh = self.state_shardings(state)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), state, sh,
            is_leaf=lambda x: isinstance(x, jax.Array) or isinstance(
                x, (np.ndarray,)))

    def shard_batch(self, batch):
        def put(a):
            a = np.asarray(a) if not isinstance(a, jax.Array) else a
            return jax.device_put(a, self.batch_sharding(a.ndim))
        return jax.tree_util.tree_map(put, batch)


def group_sharded_parallel(model, optimizer, level: str = "os_g",
                           scaler=None, group=None, offload=False,
                           sync_buffers=False, buffer_max_size=2 ** 23,
                           segment_size=2 ** 20, sync_comm=False):
    """paddle.distributed.sharding.group_sharded_parallel parity:
    level 'os' = stage1, 'os_g' = stage2, 'p_g_os' = stage3.
    Returns (model, optimizer, scaler) with the plan attached; the
    compiled path reads ``model._sharding_stage``."""
    stage = {"os": 1, "os_g": 2, "p_g_os": 3}[level]
    model._sharding_stage = stage
    optimizer._sharding_stage = stage
    return model, optimizer, scaler
