"""The one file that asks the chip's compiler.

The sandbox has no TPU, but the TPU's compiler is installed and compiles
for a chip that is described and not attached (shapes only, nothing
runs).  Each case below is a kernel of a main path at the width it is
deployed at, compiled for one v5e chip of a described ``v5e:2x2`` — about
two seconds each — so a Mosaic refusal (an unaligned slice, an op with
no lowering, too much VMEM) fails here and costs no chip time.

Rules this file keeps (``/opt/skills/guides/on-chip-measurement`` §2):
the topology is described inside a module-scoped fixture that skips when
it cannot be — never at import, in a ``skipif`` or in ``parametrize``
arguments, never ``autouse``, never in ``conftest.py``; everything built
from it is built in a fixture or in the test; compiles run in the test's
own process (the process that described the topology holds libtpu's
lock); all such tests live in this ONE file so one xdist worker gets
them; the persistent compilation cache is off around them.  A compile
that passes here is a compile, not a chip run.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernel-or-reference sites ask ``is_compiled_with_tpu()``; the
    test steers it for its own process (not a program option)."""
    from paddle_tpu.runtime import device
    monkeypatch.setattr(device, "is_compiled_with_tpu", lambda: True)


# -- cases: name -> builder(sds) -> (fn, args, kernel names expected) ---------

def _flash(grad):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    def build(sds):
        q = sds((2, 8192, 12, 128), BF16)
        kv = sds((2, 8192, 4, 128), BF16)
        if not grad:
            return (functools.partial(flash_attention_raw, causal=True),
                    (q, kv, kv), ("flash_fwd",))

        def loss(q, k, v):
            return flash_attention_raw(q, k, v, causal=True).astype(
                F32).sum()
        return (jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv),
                ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    return build


def _pools(sds, kvh, quant, n_pages=65, page=128, d=128):
    pool = sds((kvh, n_pages, page, d), I8 if quant else BF16)
    scales = (sds((kvh, n_pages, 1, page), F32),) * 2 if quant else ()
    return pool, scales


def _ragged(h, kvh, quant):
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw

    def build(sds):
        t, s, maxp = 136, 136, 16          # max_seqs 8 + one page of rows
        pool, scales = _pools(sds, kvh, quant)
        new = sds((t, kvh, 128), BF16)
        desc = sds((s,), I32)
        return (ragged_paged_append_attend_raw,
                (sds((t, h, 128), BF16), pool, pool, new, new, desc, desc,
                 desc, sds((s, maxp), I32)) + scales,
                ("ragged_paged_append_attend",))
    return build


def _decode(append):
    from paddle_tpu.ops.pallas import paged_attention as pa

    def build(sds):
        b, h, kvh, maxp = 8, 12, 4, 16
        pool, _ = _pools(sds, kvh, False)
        q, new = sds((b, h, 128), BF16), sds((b, kvh, 128), BF16)
        tbl, lens = sds((b, maxp), I32), sds((b,), I32)
        if append:
            return (pa.paged_decode_append_attend_raw,
                    (q, pool, pool, new, new, tbl, lens),
                    ("paged_decode_append",))
        return pa.paged_attention_raw, (q, pool, pool, tbl, lens), \
            ("paged_decode",)
    return build


def _moe_rows(sds):
    from paddle_tpu.ops.pallas.grouped_matmul import dropless_moe_ffn_rows
    e, h, f, rows = 64, 2048, 1408, 64
    return (dropless_moe_ffn_rows,
            (sds((rows, h), BF16), sds((rows,), I32), sds((e, h, f), BF16),
             sds((e, h, f), BF16), sds((e, f, h), BF16)), ("gmm",))


def _fused_update(kind):
    from paddle_tpu.ops.pallas import fused_train as ft
    hyper = {"sgd": {}, "momentum": {"momentum": 0.9},
             "adam": {"beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
                      "weight_decay": 0.1, "decoupled": True}}[kind]

    def build(sds):
        leaf = sds((1536, 6144), BF16)
        slots = {k: sds((1536, 6144), F32) for k in ft.SLOT_KEYS[kind]}
        scalar = sds((), F32)

        def fn(p, g, slots, lr, step, clip):
            # the public entry: on the chip it must END in the kernel
            return ft.fused_update_flat(kind, p, g, slots, lr=lr,
                                        step_f=step, clip_scale=clip,
                                        hyper=hyper)
        return fn, (leaf, leaf, slots, scalar, scalar, scalar), \
            (f"fused_update_{kind}",)
    return build


def _add_norm(sds):
    from paddle_tpu.ops.pallas.fused_train import add_rms_norm_raw
    x = sds((2, 8192, 1536), BF16)
    return add_rms_norm_raw, (x, x, sds((1536,), BF16)), ("add_norm",)


def _qkv_rope(sds):
    from paddle_tpu.ops.pallas.fused_train import qkv_rope_raw
    fn = functools.partial(qkv_rope_raw, n_heads=12, n_kv=4, head_dim=128)
    cs = sds((8192, 128), F32)
    return fn, (sds((2, 8192, 1536), BF16), sds((1536, 1536), BF16),
                sds((1536, 512), BF16), sds((1536, 512), BF16), cs, cs), \
        ("matmul_rope",)


CASES = {
    "flash_fwd": _flash(False),
    "flash_fwd_bwd": _flash(True),
    **{f"ragged_{h}_{kvh}_{'int8' if q else 'bf16'}": _ragged(h, kvh, q)
       for h, kvh in ((12, 4), (16, 4), (32, 8), (16, 16))
       for q in (False, True)},
    "decode_append_12_4": _decode(True),
    "decode_12_4": _decode(False),
    "moe_ffn_rows_64e_64rows": _moe_rows,
    **{f"fused_update_{k}": _fused_update(k)
       for k in ("sgd", "momentum", "adam")},
    "add_rms_norm": _add_norm,
    "qkv_rope": _qkv_rope,
}


def _kernels_in(text):
    return [line.split('op_name="', 1)[1].split('"', 1)[0]
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and 'op_name="' in line]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache, on_tpu):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args, expect = CASES[case](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    ops = _kernels_in(compiled.as_text())
    for name in expect:
        assert any(name in op for op in ops), (name, ops)


def test_expert_parallel_dispatch_compiles_for_four_chips(
        topo, no_compile_cache, on_tpu):
    """``distributed/expert_parallel.py`` reads the platform from the
    MESH: on four described v5e devices it takes the
    ``lax.ragged_all_to_all`` branch (the CPU meshes of every other
    test take the all-gather emulation), with the per-shard grouped
    matmul kernels behind it."""
    from paddle_tpu.distributed.expert_parallel import moe_grouped_ep_raw
    mesh = Mesh(np.array(topo.devices).reshape(4), ("ep",))
    t, h, f, e, k = 2048, 2048, 1408, 64, 6

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))
    fn = functools.partial(
        moe_grouped_ep_raw, k=k, balance_coef=0.01, z_coef=0.0,
        norm_topk=False, tm=None, interpret=False, mesh=mesh)
    compiled = jax.jit(fn).lower(
        sds((t, h), BF16, "ep"), sds((h, e), F32),
        sds((e, h, f), BF16, "ep"), sds((e, h, f), BF16, "ep"),
        sds((e, f, h), BF16, "ep")).compile()
    text = compiled.as_text()
    assert "ragged-all-to-all" in text
    assert any("gmm" in op for op in _kernels_in(text))
    per_chip = compiled.memory_analysis()
    assert per_chip.argument_size_in_bytes < 16e9


def test_ragged_kernel_runs_per_shard_under_a_tp_mesh(
        topo, no_compile_cache, on_tpu):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"): under the serving mesh the engine hands every shard
    its own heads through ``TPShardings.per_shard``.  tp=4 on the four
    described devices, Llama 12:4 heads (one KV head per chip)."""
    from paddle_tpu.distributed.sharding import TPShardings
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    sh = TPShardings(Mesh(np.array(topo.devices).reshape(4), ("tp",)))

    def sds(shape, dtype, dim=None):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sh._sharding(len(shape), dim))
    t, s, maxp = 136, 136, 16
    pool, new = sds((4, 65, 128, 128), BF16, 0), sds((t, 4, 128), BF16, 1)
    desc = sds((s,), I32)
    fn = sh.per_shard(ragged_paged_append_attend_raw,
                      (1, 0, 0, 1, 1, None, None, None, None), (2, 0, 0))
    compiled = jax.jit(fn).lower(
        sds((t, 12, 128), BF16, 1), pool, pool, new, new, desc, desc,
        desc, sds((s, maxp), I32)).compile()
    assert any("ragged_paged_append_attend" in op
               for op in _kernels_in(compiled.as_text()))
    # and without the wrapper the compiler refuses, which is why it is there
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(ragged_paged_append_attend_raw).lower(
            sds((t, 12, 128), BF16, 1), pool, pool, new, new, desc, desc,
            desc, sds((s, maxp), I32))
