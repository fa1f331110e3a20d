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


def _pools(sds, kvh, quant, n_pages=65, page=128, d=128, lead=()):
    pool = sds(lead + (kvh, n_pages, page, d), I8 if quant else BF16)
    scales = (sds(lead + (kvh, n_pages, 1, page), F32),) * 2 \
        if quant else ()
    return pool, scales


def _ragged_at_layer(*args):
    """The ragged kernel on pools stacked over layers, the layer index
    its last (traced) argument — the form the unified step calls."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    return ragged_paged_append_attend_raw(*args[:-1], layer=args[-1])


def _ragged(h, kvh, quant, layers=None):
    """``layers``: the pools (and scale pools) stacked over that many
    layers, through ``_ragged_at_layer``."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw

    def build(sds):
        t, s, maxp = 136, 136, 16          # max_seqs 8 + one page of rows
        pool, scales = _pools(sds, kvh, quant,
                              lead=() if layers is None else (layers,))
        new = sds((t, kvh, 128), BF16)
        desc = sds((s,), I32)
        args = (sds((t, h, 128), BF16), pool, pool, new, new, desc, desc,
                desc, sds((s, maxp), I32)) + scales
        if layers is None:
            return (ragged_paged_append_attend_raw, args,
                    ("ragged_paged_append_attend",))
        return (_ragged_at_layer, args + (sds((), I32),),
                ("ragged_paged_append_attend",))
    return build


def _ragged_head_256(sds):
    """The hybrid cell's geometry: 16:2 heads x 256, ``maxp`` 128 (16k
    of context), 576 rows under 71 descriptors, the pool of ONE
    full-attention layer through the stacked form."""
    t, s, h, kvh, d, maxp = 576, 71, 16, 2, 256, 128
    pool = sds((1, kvh, 64 * maxp + 1, 128, d), BF16)
    new, desc = sds((t, kvh, d), BF16), sds((s,), I32)
    return (_ragged_at_layer,
            (sds((t, h, d), BF16), pool, pool, new, new, desc, desc, desc,
             sds((s, maxp), I32), sds((), I32)),
            ("ragged_paged_append_attend",))


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
             sds((e, h, f), BF16), sds((e, f, h), BF16)),
            ("gmm_glu", "gmm"))


def _moe_rows_grad(sds):
    """The same tiny buffer differentiated: at a row tile of 32 every
    kernel of the backward takes whole-matrix blocks too (``gmm`` on
    the transposed weights, ``gmm_dw``), past Mosaic's default scope —
    each call has to ask for the VMEM it needs."""
    fn, args, _ = _moe_rows(sds)

    def loss(x, row_expert, wg, wu, wd):
        return fn(x, row_expert, wg, wu, wd).astype(F32).sum()
    return (jax.grad(loss, argnums=(0, 2, 3, 4)), args,
            ("gmm_glu", "gmm", "gmm_dw"))


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
    **{f"ragged_stacked_16_16_{'int8' if q else 'bf16'}":
       _ragged(16, 16, q, layers=4) for q in (False, True)},
    "ragged_stacked_16_2_head256_maxp128": _ragged_head_256,
    "decode_append_12_4": _decode(True),
    "decode_12_4": _decode(False),
    "moe_ffn_rows_64e_64rows": _moe_rows,
    "moe_ffn_rows_64e_64rows_grad": _moe_rows_grad,
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


def _has_kernel(ops, name):
    """``name`` as a whole word of some kernel's op name (``gmm`` is
    not ``gmm_glu``; under ``jax.grad`` the name sits in ``jvp(..)``)."""
    import re
    return any(re.search(rf"(?<!\w){name}(?!\w)", op) for op in ops)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache, on_tpu):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args, expect = CASES[case](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    ops = _kernels_in(compiled.as_text())
    for name in expect:
        assert _has_kernel(ops, name), (name, ops)


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


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "stacked_pools"])
def test_ragged_kernel_runs_per_shard_under_a_tp_mesh(
        stacked, topo, no_compile_cache, on_tpu):
    """GSPMD cannot partition a Mosaic call ("wrap the call in a
    shard_map"): under the serving mesh the engine hands every shard
    its own heads through ``TPShardings.per_shard``.  tp=4 on the four
    described devices, Llama 12:4 heads (one KV head per chip) — one
    layer's pools, and the form the unified step uses: the pools
    stacked over layers (KVH is then dim 1) with the layer index a
    replicated traced scalar."""
    from paddle_tpu.distributed.sharding import TPShardings
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_append_attend_raw
    sh = TPShardings(Mesh(np.array(topo.devices).reshape(4), ("tp",)))

    def sds(shape, dtype, dim=None):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sh._sharding(len(shape), dim))
    t, s, maxp = 136, 136, 16
    new = sds((t, 4, 128), BF16, 1)
    desc = sds((s,), I32)
    if stacked:
        pool, kvh_dim = sds((3, 4, 65, 128, 128), BF16, 1), 1
        layer, kernel = (sds((), I32),), _ragged_at_layer
    else:
        pool, kvh_dim = sds((4, 65, 128, 128), BF16, 0), 0
        layer, kernel = (), ragged_paged_append_attend_raw
    args = (sds((t, 12, 128), BF16, 1), pool, pool, new, new, desc, desc,
            desc, sds((s, maxp), I32)) + layer
    fn = sh.per_shard(
        kernel, (1, kvh_dim, kvh_dim, 1, 1) + (None,) * (4 + len(layer)),
        (2, kvh_dim, kvh_dim))
    compiled = jax.jit(fn).lower(*args).compile()
    assert any("ragged_paged_append_attend" in op
               for op in _kernels_in(compiled.as_text()))
    # and without the wrapper the compiler refuses, which is why it is there
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(kernel).lower(*args)


def test_expert_layer_runs_per_shard_under_a_tp_mesh(
        topo, no_compile_cache, on_tpu):
    """``moe_ffn`` under the serving mesh (tp=4 on the four described
    devices, expert stacks replicated): both of its Pallas calls —
    ``gmm_glu`` and ``gmm`` — go through ``TPShardings.per_shard``, at
    DeepSeekMoE's widths, reaching into a flattened two-layer stack."""
    from paddle_tpu.distributed.sharding import TPShardings
    from paddle_tpu.inference.moe_dispatch import MoEArch, moe_ffn
    sh = TPShardings(Mesh(np.array(topo.devices).reshape(4), ("tp",)))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=sh._sharding(len(shape), None))
    t, h, e, f, layers = 160, 2048, 64, 1408, 2
    arch = MoEArch(num_experts=e, top_k=6, norm_topk=False, capacity=0,
                   shared=False, shared_gate=False, attn_bias=False,
                   dispatch="grouped")
    zed = sds((1, 1), F32)
    mw = (sds((h, e), BF16), sds((layers * e, h, f), BF16),
          sds((layers * e, h, f), BF16), sds((layers * e, f, h), BF16),
          zed, zed, zed, zed)

    def fn(hn, mw, live, layer):
        return moe_ffn(hn, mw, arch, live, shardings=sh,
                       expert_base=layer * e)
    compiled = jax.jit(fn).lower(
        sds((t, h), BF16), mw, sds((t,), jnp.bool_), sds((), I32)).compile()
    ops = _kernels_in(compiled.as_text())
    assert _has_kernel(ops, "gmm_glu") and _has_kernel(ops, "gmm"), ops


# -- whole step programs: the layer loop uses its operands where they lie -----

_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
         "u8": 1, "pred": 1}


def _bytes_moved(text):
    """(bytes written, name) of every instruction of the optimised
    program — in the entry computation and in loop bodies, not inside
    fusions — that XLA named after a copy, a dynamic-slice or a
    dynamic-update-slice (fusions carry the ops they were made of in
    their names: ``dynamic-slice_bitcast_fusion.12``)."""
    import re
    rows, inside = [], ""
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            inside = line.split()[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[a-z0-9]+\[[^=]*?) "
                     r"[\w\-]+\(", line)
        if not m or "fused" in inside or \
                not any(k in m.group(1) for k in _MOVES):
            continue
        size = 0
        for dt, dims in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", m.group(2)):
            n = _ITEM.get(dt, 4)
            for d in filter(None, dims.split(",")):
                n *= int(d)
            size += n
        rows.append((size, m.group(1)))
    return rows


def _expert_rows_guard(text, arch, t, hidden):
    """The guard on PR 31's gain.  The routed rows reach the expert
    kernels ONCE, in the activations' own dtype: the program holds
    ``gmm_glu`` (gate and up in one pass, ``silu x up`` in VMEM) and
    ``gmm`` (down), and NO operation of it — fused or not — writes a
    float32 ``[m_pad, hidden]`` array except the down kernel itself.
    Before, the sorted buffer was float32 (``xs``: written once, read
    by two calls) and ``hg`` / ``hu`` / ``hs`` went through HBM.
    Returns ``m_pad``, the buffer's rows at this program's geometry."""
    import re
    from paddle_tpu.inference.moe_dispatch import expert_buffer_rows
    ops = _kernels_in(text)
    for name in ("gmm_glu", "gmm"):
        assert _has_kernel(ops, name), (name, ops)
    m_pad = expert_buffer_rows(arch, t)
    assert m_pad % 32 == 0 and m_pad < 3 * t * arch.top_k + 32 * arch.n_held
    assert f"bf16[{m_pad},{hidden}]" in text        # the buffer itself
    writers = [ln.strip()[:160] for ln in text.splitlines()
               if re.search(rf"= f32\[{m_pad},{hidden}\]", ln)
               and " parameter(" not in ln
               and "/gmm/pallas_call" not in ln]
    assert not writers, writers
    return m_pad


def _ragged_grid(text):
    """(descriptors, KV heads) of every ``ragged_paged_append_attend``
    call in the compiled text: its grid, read off the first output —
    one ``[P x G, D]`` block a (descriptor, KV head)."""
    import re
    return {tuple(int(v) for v in m.groups()) for m in re.finditer(
        r"= \(bf16\[(\d+),(\d+),\d+,\d+\][^\n]*custom-call\([^\n]*"
        r"ragged_paged_append_attend", text)}


def _step_program(sds, moe, window, packed, n_layers=2):
    """``_paged_mixed_step`` / ``_paged_mixed_window`` — or, ``packed``,
    the one-transfer wrappers the engine launches — at the serving
    cell's widths (DeepSeekMoE-16B: hidden 2048, 16:16 heads x 128, 64
    experts of 2048 x 1408 + a shared 2816; dense: InternLM2-1.8B's
    16:8 heads, 8192), two layers, the cell's 32 slots x 2048 (513
    pages: one layer's K pool is then larger than the ragged kernel's
    own per-descriptor row blocks, which are S3's to shrink), each at
    the geometry the engine launches it at: the mixed step slots +
    prefill budget rows, the window ONE row a slot (PR 33)."""
    from paddle_tpu.inference import engine as E
    from paddle_tpu.inference.moe_dispatch import MoEArch
    h, nh, d, e, f, vocab = 2048, 16, 128, 64, 1408, 32000
    kvh = nh if moe else 8
    slots, max_len, page = 32, 2048, 128
    n_pages, maxp = slots * (max_len // page) + 1, max_len // page
    t = slots if window else slots + page

    def w(*shape, dtype=BF16):
        return sds((n_layers,) + shape, dtype)
    attn = (w(h, nh * d), w(h, kvh * d), w(h, kvh * d), w(nh * d, h))
    if moe:
        zed = w(1, 1, dtype=F32)
        stack = (w(h), attn[0], zed, attn[1], zed, attn[2], zed, attn[3],
                 w(h), w(h, e), w(e, h, f), w(e, h, f), w(e, f, h),
                 w(h, 2 * f), w(h, 2 * f), w(2 * f, h), zed)
        arch = MoEArch(num_experts=e, top_k=6, norm_topk=False,
                       capacity=0, shared=True, shared_gate=False,
                       attn_bias=False, dispatch="grouped")
        smallest = e * h * f * 2
    else:
        stack = (w(h),) + attn + (w(h), w(h, 8192), w(h, 8192),
                                  w(8192, h))
        arch, smallest = None, None
    pool = sds((n_layers, kvh, n_pages, page, d), BF16)
    one_pool = kvh * n_pages * page * d * 2
    rows, tbl = sds((t,), I32), sds((t, maxp), I32)
    rope = (sds((max_len, d), F32),) * 2
    args = [stack, sds((h,), BF16), sds((h, vocab), BF16),
            sds((vocab, h), BF16), rope, pool, pool, None, None,
            rows, rows, tbl, rows, rows, rows, tbl, rows, rows,
            sds((2,), jnp.uint32), sds((), I32)]
    kw = dict(eps=1e-6, kvh=kvh, head_dim=d, arch=arch)
    if window:
        kw["n_steps"] = 8
    if packed:
        geom = (t, t, maxp)
        args[9:] = [sds((E._step_layout(*geom, False)[1],), I32),
                    sds((2,), jnp.uint32)]
        lowered = (E._packed_mixed_window if window
                   else E._packed_mixed_step).lower(*args, geom=geom, **kw)
    elif window:
        args += [rows, rows, sds((), I32)]
        lowered = E._paged_mixed_window.lower(*args, **kw)
    else:
        lowered = E._paged_mixed_step.lower(*args, **kw)
    return lowered, 2 * 2 * pool.size, min(filter(
        None, (smallest, one_pool))), (arch, t, h)


@pytest.mark.parametrize("packed", [False, True], ids=["inner", "packed"])
@pytest.mark.parametrize("window", [False, True],
                         ids=["mixed_step", "mixed_window"])
@pytest.mark.parametrize("moe", [True, False], ids=["moe", "dense"])
def test_step_program_moves_no_layer_of_weights_or_pool(
        moe, window, packed, one_chip, no_compile_cache, on_tpu):
    """The guard on PR 26's gain.  Before it, the layer scan had every
    layer's expert stacks and K/V pools copied out of their ``[L, ..]``
    stacks for the two custom calls, wrote the pools back into its
    stacked ``ys`` and copied those whole to the program's outputs
    (60 % of the chip's busy time in both serving cells).  Now the
    pools ride the carry whole and both kernels index the layer
    themselves: no copy, dynamic-slice or dynamic-update-slice — plain
    or as a fusion — may write as many bytes as one layer's smallest
    expert stack or one layer's K pool.  The wrappers that take one
    upload and give one read-back (ISSUE 29) are what the engine
    launches, and are held to the same."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lowered, pool_bytes, limit, rows = _step_program(sds, moe, window,
                                                     packed)
    compiled = lowered.compile()
    text = compiled.as_text()
    ops = _kernels_in(text)
    assert any("ragged_paged_append_attend" in op for op in ops), ops
    # one descriptor a row: 32 in a window, 160 in a mixed step
    assert _ragged_grid(text) == {(32 if window else 160,
                                   16 if moe else 8)}
    if moe:
        assert _expert_rows_guard(text, *rows) == (
            2240 if window else 3008)
    moved = sorted(_bytes_moved(text), reverse=True)
    assert moved, "the parser found no copy or slice at all"
    assert moved[0][0] < limit, (limit, moved[:6])
    # the pools are updated where they lie: donated in, aliased out
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def _hybrid_step_program(sds, window, packed):
    """``_paged_mixed_step`` / ``_paged_mixed_window`` (``packed``: the
    one-transfer wrappers the engine launches) for the hybrid
    backbone at the cell ``hybrid_serve_longctx``'s sizes: one period
    (linear, linear, linear, full) at Qwen3-Next-80B-A3B's widths, 256
    of 512 experts held, vocabulary 75,968, and the engine settings of
    the configuration's file (slots, context, page, prompt rows a
    step)."""
    import json
    import os
    from paddle_tpu.inference import engine as E
    from paddle_tpu.inference.backbone import HybridArch
    from paddle_tpu.inference.moe_dispatch import MoEArch
    h, nh, kvh, d, held, f, vocab = 2048, 16, 2, 256, 256, 512, 75968
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench", "configs",
            "qwen3_next_80b_a3b_4l.json"), encoding="utf-8") as fh:
        eng = json.load(fh)["engine"]
    slots, max_len, page, budget = (
        eng["max_seqs"], eng["max_len"], eng["page_size"],
        eng["prefill_token_budget"])
    n_pages, maxp = slots * (max_len // page) + 1, max_len // page
    # the engine's two geometries: slots + budget rows under its
    # descriptor cap, or a window's one row a slot + the dead descriptor
    t = slots if window else slots + budget
    n_desc = slots + 1 if window else slots + 3 + budget // page
    hy = HybridArch(kinds=("linear",) * 3 + ("full",), rotary_dim=64,
                    linear_num_key_heads=16, linear_num_value_heads=32,
                    linear_key_head_dim=128, linear_value_head_dim=128,
                    linear_conv_kernel_dim=4, conv_channels=8192,
                    zero_centred_norm=True)

    def w(*shape):
        return sds(shape, BF16)

    def layer(kind):
        lay = dict(
            in_norm=w(h), post_norm=w(h), router=w(h, 512),
            experts_gate=w(held, h, f), experts_up=w(held, h, f),
            experts_down=w(held, f, h), shared_gate=w(h, f),
            shared_up=w(h, f), shared_down=w(f, h),
            shared_expert_gate=w(h, 1))
        if kind == "linear":
            lay.update(qkvz=w(h, 12288), ba=w(h, 64), conv=w(4, 8192),
                       A_log=w(32), dt_bias=w(32), norm=w(128),
                       o=w(4096, h))
        else:
            lay.update(q=w(h, nh * 2 * d), k=w(h, kvh * d),
                       v=w(h, kvh * d), o=w(nh * d, h), q_norm=w(d),
                       k_norm=w(d))
        return lay
    arch = MoEArch(num_experts=512, top_k=10, norm_topk=True, capacity=0,
                   shared=True, shared_gate=True, attn_bias=False,
                   dispatch="grouped", expert_lo=0, experts_held=held)
    pool = sds((1, kvh, n_pages, page, d), BF16)
    rows, tbl = sds((t,), I32), sds((t, maxp), I32)
    desc, dtbl = sds((n_desc,), I32), sds((n_desc, maxp), I32)
    rec = tuple(sds((slots + 1, 32, 128, 128), F32) for _ in range(3))
    conv = tuple(sds((slots + 1, 3, 8192), BF16) for _ in range(3))
    args = [tuple(layer(k) for k in hy.kinds), w(h), w(h, vocab),
            w(vocab, h), (sds((max_len, 64), F32),) * 2, pool, pool,
            None, None, rows, rows, tbl, desc, desc, desc, dtbl, rows,
            rows, sds((2,), jnp.uint32), sds((), I32)]
    kw = dict(eps=1e-6, kvh=kvh, head_dim=d, arch=arch, hybrid=hy)
    state = 2 * pool.size * 2 + sum(a.size * 4 for a in rec) \
        + sum(a.size * 2 for a in conv)
    if window:
        kw["n_steps"] = 8
    if packed:
        geom = (t, n_desc, maxp)
        args[9:] = [sds((E._step_layout(*geom, True)[1],), I32),
                    sds((2,), jnp.uint32), rec, conv]
        return (E._packed_mixed_window if window
                else E._packed_mixed_step).lower(
            *args, geom=geom, **kw), state, (arch, t, h)
    if window:
        return E._paged_mixed_window.lower(
            *args, rows, rows, sds((), I32), rec, conv, desc,
            **kw), state, (arch, t, h)
    return E._paged_mixed_step.lower(*args, rec, conv, desc,
                                     **kw), state, (arch, t, h)


@pytest.mark.parametrize("packed", [False, True], ids=["inner", "packed"])
@pytest.mark.parametrize("window", [False, True],
                         ids=["mixed_step", "mixed_window"])
def test_hybrid_step_program_fits_and_updates_its_state_in_place(
        window, packed, one_chip, no_compile_cache, on_tpu):
    """The layer loop of several kinds at the new cell's real sizes:
    the chip's compiler takes it (the ragged kernel at head 256 and
    ``gmm`` over the held share inside), weights held once plus pools,
    state and temporaries fit the chip, and BOTH kinds of per-request
    state — KV pools and the recurrent state / conv windows — are
    donated in and aliased out; the routed rows travel as PR 31 left
    them (``_expert_rows_guard``)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lowered, state_bytes, rows = _hybrid_step_program(sds, window, packed)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert any("ragged_paged_append_attend" in op
               for op in _kernels_in(text))
    assert _ragged_grid(text) == {(65 if window else 71, 2)}
    assert _expert_rows_guard(text, *rows) == (8832 if window else 13952)
    mem = compiled.memory_analysis()
    print("hybrid step program:", mem.argument_size_in_bytes,
          "B arguments,", mem.temp_size_in_bytes, "B temporaries")
    assert mem.alias_size_in_bytes >= state_bytes
    held_once = 4 * 3 * 256 * 2048 * 512 * 2
    assert held_once < mem.argument_size_in_bytes < 10.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9


def _ssm_moe_step_program(sds, window, packed=True, slots=None):
    """The packed step / window program for the backbone whose blocks
    are a mixer or an expert layer alone, at the cell
    ``ssm_moe_serve_reason``'s sizes: the eleven blocks ``MEMEMEMEM*E``
    at Nemotron-3-Super's widths, 128 of 512 experts held in a 1,024
    latent, vocabulary 32,768, and the engine settings of the
    configuration's file."""
    import json
    import os
    from paddle_tpu.inference import engine as E
    from paddle_tpu.inference.backbone import HybridArch
    from paddle_tpu.inference.moe_dispatch import MoEArch
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench", "configs",
            "nemotron3_super_120b_a12b_11l.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    eng = cfg["engine"]
    h, nh, kvh, d = 4096, 32, 2, 128
    held, z, f, fs, vocab = 128, 1024, 2688, 5376, 32768
    mh, mp, g, n = 128, 64, 8, 128
    d_in, cc = mh * mp, mh * mp + 2 * g * n
    slots = slots or eng["max_seqs"]
    max_len, page, budget = (eng["max_len"], eng["page_size"],
                             eng["prefill_token_budget"])
    n_pages, maxp = slots * (max_len // page) + 1, max_len // page
    t = slots if window else slots + budget
    n_desc = slots + 1 if window else slots + 3 + budget // page
    kinds = tuple({"M": "ssm", "*": "full", "E": "ffn"}[p]
                  for p in cfg["hybrid_override_pattern"])
    hy = HybridArch(kinds=kinds, rotary_dim=0, linear_num_key_heads=0,
                    linear_num_value_heads=0, linear_key_head_dim=0,
                    linear_value_head_dim=0, linear_conv_kernel_dim=4,
                    conv_channels=cc, zero_centred_norm=False,
                    mamba_num_heads=mh, mamba_head_dim=mp, n_groups=g,
                    ssm_state_size=n)

    def w(*shape):
        return sds(shape, BF16)

    def layer(kind):
        if kind == "ssm":
            return dict(in_norm=w(h), in_proj=w(h, d_in + cc + mh),
                        conv=w(4, cc), conv_bias=w(cc), A_log=w(mh),
                        dt_bias=w(mh), D=w(mh), norm=w(d_in),
                        o=w(d_in, h))
        if kind == "full":
            return dict(in_norm=w(h), q=w(h, nh * d), k=w(h, kvh * d),
                        v=w(h, kvh * d), o=w(nh * d, h))
        return dict(post_norm=w(h), router=w(h, 512), router_bias=w(512),
                    latent_in=w(h, z), latent_out=w(z, h),
                    experts_up=w(held, z, f), experts_down=w(held, f, z),
                    shared_up=w(h, fs), shared_down=w(fs, h))
    arch = MoEArch(num_experts=512, top_k=22, norm_topk=True, capacity=0,
                   shared=True, shared_gate=False, attn_bias=False,
                   dispatch="grouped", expert_lo=0, experts_held=held,
                   scoring="sigmoid", route_scale=5.0, expert_act="relu2")
    pool = sds((1, kvh, n_pages, page, d), BF16)
    n_ssm = kinds.count("ssm")
    rec = tuple(sds((slots + 1, mh, mp, n), F32) for _ in range(n_ssm))
    conv = tuple(sds((slots + 1, 3, cc), BF16) for _ in range(n_ssm))
    geom = (t, n_desc, maxp)
    args = [tuple(layer(k) for k in kinds), w(h), w(h, vocab),
            w(vocab, h), (sds((max_len, 0), F32),) * 2, pool, pool,
            None, None, sds((E._step_layout(*geom, True)[1],), I32),
            sds((2,), jnp.uint32), rec, conv]
    kw = dict(eps=1e-5, kvh=kvh, head_dim=d, arch=arch, hybrid=hy,
              geom=geom)
    if window:
        kw["n_steps"] = 8
    state = 2 * pool.size * 2 + sum(a.size * 4 for a in rec) \
        + sum(a.size * 2 for a in conv)
    return (E._packed_mixed_window if window
            else E._packed_mixed_step).lower(*args, **kw), state, \
        (arch, t, z)


@pytest.mark.parametrize("window", [False, True],
                         ids=["mixed_step", "mixed_window"])
def test_ssm_moe_step_program_fits_and_updates_its_state_in_place(
        window, one_chip, no_compile_cache, on_tpu):
    """Blocks that are a mixer or an expert layer alone, at the cell
    ``ssm_moe_serve_reason``'s real sizes: the chip's compiler takes the
    programs the engine launches (the ragged kernel at 32 : 2 heads of
    128, ``gmm`` at K 1024 / N 2688 and K 2688 / N 1024 over the held
    share), weights held once plus pools, the Mamba-2 state and the
    temporaries fit the chip, and both kinds of per-request state are
    donated in and aliased out.  The routed rows travel in bf16 at the
    latent's width and nothing but the up kernel writes a float32
    ``[m_pad, 2688]``."""
    import re
    from paddle_tpu.inference.moe_dispatch import expert_buffer_rows

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lowered, state_bytes, (arch, t, z) = _ssm_moe_step_program(sds, window)
    compiled = lowered.compile()
    text = compiled.as_text()
    ops = _kernels_in(text)
    assert any("ragged_paged_append_attend" in op for op in ops)
    assert _has_kernel(ops, "gmm") and not _has_kernel(ops, "gmm_glu")
    assert _ragged_grid(text) == {(129 if window else 135, 2)}
    m_pad = expert_buffer_rows(arch, t)
    assert m_pad == (6912 if window else 18176)
    assert f"bf16[{m_pad},{z}]" in text              # the buffer itself
    mem = compiled.memory_analysis()
    print("ssm-moe step program:", mem.argument_size_in_bytes,
          "B arguments,", mem.temp_size_in_bytes, "B temporaries,",
          mem.alias_size_in_bytes, "B aliased")
    assert mem.alias_size_in_bytes >= state_bytes
    held_once = 5 * 2 * 128 * 1024 * 2688 * 2
    assert held_once < mem.argument_size_in_bytes < 13.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
