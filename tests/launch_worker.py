"""Worker script for the launch/rendezvous test (run via
``python -m paddle_tpu.distributed.launch --nproc_per_node 2``).

Each process gets 4 virtual CPU devices; after init_parallel_env the
global device set is 8 across 2 processes — one mesh spans both, and a
psum over it must see contributions from every process (the reference's
multi-node single-host simulation, SURVEY.md §4 collective tests).
"""
import os
import sys

# must precede the first jax import
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa


def main(out_dir):
    from paddle_tpu.distributed import env as dist_env

    multi = dist_env.init_parallel_env()
    assert multi, "launch env not detected"
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()
    devs = jax.devices()
    assert len(devs) == 8, f"global devices {len(devs)}"

    mesh = Mesh(np.array(devs).reshape(8), ("dp",))
    sh = NamedSharding(mesh, P("dp"))

    # each device contributes its global index; psum must equal 0+..+7
    def make_local(i):
        return jnp.full((1,), float(i))

    pos = {d: i for i, d in enumerate(devs)}   # device ids != positions
    local = [jax.device_put(make_local(pos[d]), d)
             for d in jax.local_devices()]
    glob = jax.make_array_from_single_device_arrays((8,), sh, local)

    total = jax.jit(
        jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P()),
        out_shardings=NamedSharding(mesh, P()))(glob)
    val = float(np.asarray(jax.device_get(total))[0])
    assert val == sum(range(8)), val

    # fleet.init on the global mesh: dp over all 8 devices
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.mesh.devices.size == 8

    # eager per-rank collectives (reference contract: each process
    # contributes its LOCAL value)
    from paddle_tpu.distributed import collective as coll
    mine = np.full((3,), float(rank + 1), np.float32)
    red = coll.all_reduce(mine)                    # 1 + 2 = 3
    assert np.allclose(np.asarray(red), 3.0), red
    mx = coll.all_reduce(mine, op=coll.ReduceOp.MAX)
    assert np.allclose(np.asarray(mx), 2.0), mx
    bc = coll.broadcast(mine, src=1)
    assert np.allclose(np.asarray(bc), 2.0), bc
    gathered = coll.all_gather(mine)
    assert np.allclose(np.asarray(gathered),
                       np.repeat([1.0, 2.0], 3)), gathered
    sub = coll.new_group(ranks=[0])                # subset group
    sr = coll.all_reduce(mine, group=sub)
    if rank == 0:
        assert np.allclose(np.asarray(sr), 1.0), sr    # only own value
    else:
        assert np.allclose(np.asarray(sr), 2.0), sr    # non-member: as-is
    coll.barrier()

    if rank == 0:
        with open(os.path.join(out_dir, "result.txt"), "w") as f:
            f.write(f"psum={val} world={dist_env.get_world_size()}")


if __name__ == "__main__":
    main(sys.argv[1])
