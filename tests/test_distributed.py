"""Distributed stack tests on the virtual 8-device CPU mesh.

Mirrors the reference's test/collective + test/auto_parallel strategy
(SURVEY.md §4): (1) metadata-only sharding-plan tests; (2) collective
semantics inside shard_map; (3) the key pattern — hybrid-parallel
training runs must match the single-device run's losses (serial-vs-
parallel numerical equivalence).
"""
import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.sharding import ShardingPlan
from paddle_tpu.distributed.trainer import ShardedTrainStep
from paddle_tpu.models.gpt import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt2_tiny_config)


from helpers import make_strategy


class TestTopology:
    def test_mesh_axes_and_sizes(self):
        hcg = fleet.init(strategy=make_strategy(dp=2, mp=2, sharding=2))
        assert hcg.mesh.shape == {"pp": 1, "dp": 2, "sharding": 2,
                                  "ep": 1, "sep": 1, "mp": 2}
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_data_parallel_group().nranks == 2

    def test_default_init_uses_all_devices(self):
        hcg = fleet.init()
        assert hcg.get_data_parallel_world_size() == 8

    def test_too_many_devices_raises(self):
        with pytest.raises(Exception):
            fleet.init(strategy=make_strategy(dp=16))


class TestShardTensor:
    def test_shard_and_reshard(self):
        hcg = fleet.init(strategy=make_strategy(dp=2, mp=4))
        x = paddle.ops.randn([8, 4])
        xs = dist.shard_tensor(x, hcg.mesh, [None, dist.Shard(0),
                                             None, None, None])
        # values unchanged, now sharded
        np.testing.assert_allclose(np.asarray(xs.value), x.numpy())
        assert not xs.value.sharding.is_fully_replicated
        xr = dist.reshard(xs, hcg.mesh, [None, dist.Replicate(),
                                         None, None, None])
        assert xr.value.sharding.is_fully_replicated

    def test_process_mesh_api(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4),
                                dim_names=["x", "y"])
        assert mesh.shape == [2, 4]
        t = dist.shard_tensor(paddle.ops.randn([4, 8]), mesh,
                              [dist.Shard(0), dist.Shard(1)])
        assert t.shape == [4, 8]


class TestCollectives:
    def test_psum_inside_shard_map(self):
        from jax.sharding import Mesh
        from jax import shard_map
        hcg = fleet.init(strategy=make_strategy(dp=8))
        mesh = hcg.mesh
        group = hcg.get_data_parallel_group()

        def body(x):
            return dist.collective.psum(x, group)

        f = shard_map(body, mesh=mesh,
                      in_specs=PartitionSpec("dp"),
                      out_specs=PartitionSpec("dp"))
        x = np.arange(8, dtype=np.float32)
        out = f(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))

    def test_eager_all_reduce_identity_on_global(self):
        fleet.init(strategy=make_strategy(dp=8))
        t = paddle.ops.randn([4])
        out = dist.all_reduce(t)
        np.testing.assert_allclose(out.numpy(), t.numpy())

    def test_all_gather_traced(self):
        from jax import shard_map
        hcg = fleet.init(strategy=make_strategy(dp=8))
        group = hcg.get_data_parallel_group()

        def body(x):
            return dist.collective.all_gather(x, group=group)

        f = shard_map(body, mesh=hcg.mesh, in_specs=PartitionSpec("dp"),
                      out_specs=PartitionSpec(None), check_vma=False)
        x = np.arange(8, dtype=np.float32)
        out = np.asarray(f(x))
        np.testing.assert_allclose(out, x)


class TestShardingPlan:
    def test_stage3_shards_params(self):
        hcg = fleet.init(strategy=make_strategy(sharding=4))
        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
        plan = ShardingPlan(model, hcg.mesh, stage=3)
        spec = plan.param_specs["0.weight"]
        assert "sharding" in jax.tree_util.tree_leaves(list(spec))

    def test_stage1_replicates_params_shards_moments(self):
        hcg = fleet.init(strategy=make_strategy(sharding=4))
        model = nn.Linear(16, 32)
        plan = ShardingPlan(model, hcg.mesh, stage=1)
        assert list(plan.param_specs["weight"]) in ([], [None, None])
        assert "sharding" in jax.tree_util.tree_leaves(
            list(plan.slot_specs["weight"]))

    def test_tp_spec_respected(self):
        hcg = fleet.init(strategy=make_strategy(mp=4))
        from paddle_tpu.distributed.parallel_layers import ColumnParallelLinear
        layer = ColumnParallelLinear(16, 32, gather_output=False)
        plan = ShardingPlan(layer, hcg.mesh, stage=1)
        assert list(plan.param_specs["weight"]) == [None, "mp"]


def run_training(model, steps=10, make_step=None, seed=0):
    """Train tiny GPT; return losses. make_step(model, opt) -> callable."""
    crit = GPTPretrainingCriterion()
    opt = optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01,
                          grad_clip=paddle.ClipGradByGlobalNorm(1.0))
    step = make_step(model, crit, opt)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        ids = (np.arange(32)[None, :] +
               rng.integers(0, 8, (8, 1))) % 32
        ids = ids.astype(np.int32)
        batch = {"x": ids[:, :-1], "y": ids[:, 1:].astype(np.int64)}
        losses.append(float(step(batch)))
    return losses


def compiled_single(model, crit, opt):
    from paddle_tpu.jit.train import CompiledTrainStep
    return CompiledTrainStep(model, lambda m, b: crit(m(b["x"]), b["y"]),
                             opt, seed=0)


class TestHybridParallelParity:
    """The reference's key distributed test pattern: parallel training must
    match serial training numerically (SURVEY.md §4 fleet tests)."""

    def _parity(self, strategy, stage=1, steps=8):
        cfg = gpt2_tiny_config()
        paddle.seed(42)
        model_ref = GPTForCausalLM(cfg)
        losses_ref = run_training(model_ref, steps=steps,
                                  make_step=compiled_single)

        # fresh fleet + identical weights
        fleet.init(strategy=strategy)
        paddle.seed(42)
        model_par = GPTForCausalLM(cfg)
        model_par.set_state_dict(model_ref.state_dict())
        # reinit weights identical to ref start: reload from scratch
        paddle.seed(42)
        model_par2 = GPTForCausalLM(cfg)

        def make_sharded(model, crit, opt):
            return ShardedTrainStep(
                model, lambda m, b: crit(m(b["x"]), b["y"]), opt,
                stage=stage, seed=0)

        losses_par = run_training(model_par2, steps=steps,
                                  make_step=make_sharded)
        np.testing.assert_allclose(losses_ref, losses_par, rtol=2e-3,
                                   atol=2e-3)
        assert losses_par[-1] < losses_par[0]

    def test_dp_parity(self):
        self._parity(make_strategy(dp=4))

    def test_dp_sharding_stage2_parity(self):
        self._parity(make_strategy(dp=2, sharding=2), stage=2)

    def test_fsdp_stage3_parity(self):
        self._parity(make_strategy(sharding=4), stage=3)

    def test_dp_mp_parity(self):
        self._parity(make_strategy(dp=2, mp=2))


class TestTPLayersParity:
    def test_column_row_matches_plain_mlp(self):
        """Megatron column→row pair == plain 2-layer MLP numerics."""
        from paddle_tpu.distributed.parallel_layers import (
            ColumnParallelLinear, RowParallelLinear)
        hcg = fleet.init(strategy=make_strategy(mp=4))
        paddle.seed(0)
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 8, input_is_parallel=True)
        plain1 = nn.Linear(16, 32)
        plain2 = nn.Linear(32, 8)
        plain1.weight.set_value(col.weight.numpy())
        plain1.bias.set_value(col.bias.numpy())
        plain2.weight.set_value(row.weight.numpy())
        plain2.bias.set_value(row.bias.numpy())

        x = paddle.ops.randn([4, 16])
        expected = plain2(nn.functional.relu(plain1(x))).numpy()

        @paddle.jit.to_static
        def tp_forward(xx):
            return row(nn.functional.relu(col(xx)))

        out = tp_forward(x).numpy()
        np.testing.assert_allclose(out, expected, rtol=1e-4, atol=1e-5)
