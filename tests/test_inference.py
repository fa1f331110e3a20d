"""Inference: ragged paged attention kernel + PagedKVCache + Predictor
(SURVEY.md §1 L8; PAPERS.md ragged-paged-attention blueprint)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference import (Config, PagedKVCache, Predictor,
                                  create_predictor)
from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention_raw, paged_attention_reference, paged_write)



def _rand_pages(rng, kvh=2, n_pages=16, page=8, d=16):
    k = rng.normal(size=(kvh, n_pages, page, d)).astype(np.float32)
    v = rng.normal(size=(kvh, n_pages, page, d)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


def _dense_oracle(q, k_pages, v_pages, page_table, seq_lens):
    """Straight dense attention on the gathered pages (independent of
    the module's own reference impl)."""
    b, h, d = q.shape
    kvh = k_pages.shape[0]
    g = h // kvh
    outs = []
    for i in range(b):
        L = int(seq_lens[i])
        ks, vs = [], []
        for t in range(L):
            pg = int(page_table[i, t // k_pages.shape[2]])
            sl = t % k_pages.shape[2]
            ks.append(np.asarray(k_pages[:, pg, sl]))
            vs.append(np.asarray(v_pages[:, pg, sl]))
        k = np.stack(ks, 1)          # [KVH, L, D]
        v = np.stack(vs, 1)
        qh = np.asarray(q[i]).reshape(kvh, g, d)
        s = np.einsum("kgd,kld->kgl", qh, k) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("kgl,kld->kgd", p, v).reshape(h, d))
    return np.stack(outs)


def _ragged_operands(rng, pool, t, kvh, g, d, shape):
    """Flat q / new-K / new-V rows and two pools of ``shape`` (with
    their per-token scale pools when ``pool`` is int8) for the ragged
    kernel tests."""
    act = jnp.float32 if pool == "float32" else jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(t, kvh * g, d)), act)
    kn = jnp.asarray(rng.normal(size=(t, kvh, d)), act)
    vn = jnp.asarray(rng.normal(size=(t, kvh, d)), act)
    if pool == "int8":
        pools = tuple(jnp.asarray(rng.integers(-127, 128, shape),
                                  jnp.int8) for _ in range(2))
        scales = tuple(jnp.asarray(
            rng.uniform(0.005, 0.03, shape[:-2] + (1, shape[-2])),
            jnp.float32) for _ in range(2))
    else:
        pools = tuple(jnp.asarray(rng.normal(size=shape), pool)
                      for _ in range(2))
        scales = ()
    return q, kn, vn, pools, scales


class TestPagedAttentionKernel:
    def _case(self, seq_lens, page=8, kvh=2, g=2, d=16, maxp=4):
        rng = np.random.default_rng(0)
        b = len(seq_lens)
        h = kvh * g
        k_pages, v_pages = _rand_pages(rng, kvh, 16, page, d)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        # distinct pages per sequence
        table = np.zeros((b, maxp), np.int32)
        nxt = 1
        for i, L in enumerate(seq_lens):
            for j in range((L + page - 1) // page):
                table[i, j] = nxt
                nxt += 1
        lens = jnp.asarray(np.array(seq_lens, np.int32))
        table = jnp.asarray(table)
        return q, k_pages, v_pages, table, lens

    def test_reference_matches_dense(self):
        args = self._case([5, 16, 23, 1])
        got = paged_attention_reference(*args)
        want = _dense_oracle(*args)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)

    def test_kernel_matches_reference_ragged(self):
        args = self._case([5, 16, 23, 1])
        with pltpu.force_tpu_interpret_mode():
            got = paged_attention_raw(*args)
        want = paged_attention_reference(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_full_pages_and_single_token(self):
        args = self._case([32, 8], maxp=4)
        with pltpu.force_tpu_interpret_mode():
            got = paged_attention_raw(*args)
        want = paged_attention_reference(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_fused_append_attend_matches_reference(self):
        """One kernel appends K/V and attends incl. the new token; the
        returned pools equal the scatter-written ones exactly."""
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_decode_append_attend,
            paged_decode_append_attend_reference)
        rng = np.random.default_rng(7)
        kvh, g, d, page, maxp = 2, 2, 16, 8, 4
        b = 4
        h = kvh * g
        k_pages, v_pages = _rand_pages(rng, kvh, 32, page, d)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        kn = jnp.asarray(rng.normal(size=(b, kvh, d)).astype(np.float32))
        vn = jnp.asarray(rng.normal(size=(b, kvh, d)).astype(np.float32))
        table = np.zeros((b, maxp), np.int32)
        nxt = 1
        for i in range(b):
            for j in range(maxp):
                table[i, j] = nxt
                nxt += 1
        table = jnp.asarray(table)
        # page-edge cases: empty, mid-page, page boundary, full-1
        lens = jnp.asarray([0, 5, 8, 23], jnp.int32)
        want_o, want_k, want_v = paged_decode_append_attend_reference(
            q, k_pages, v_pages, kn, vn, table, lens)
        with pltpu.force_tpu_interpret_mode():
            got_o, got_k, got_v = paged_decode_append_attend(
                q, k_pages, v_pages, kn, vn, table, lens)
        np.testing.assert_allclose(np.asarray(got_o),
                                   np.asarray(want_o), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_array_equal(np.asarray(got_k),
                                      np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(got_v),
                                      np.asarray(want_v))

    @pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
    def test_ragged_append_attend_matches_reference(self, pool):
        """The ragged mixed-step kernel (XLA row-block gather, the
        BlockSpecs, the pool aliases, the output transpose) against the
        per-row jnp reference, at the chip's page and head width with a
        query group that is not a power of two.  Descriptors keep the
        kernel's contract ``kv_len % P + q_len <= P``: decode rows
        mid-page and on a page's last row, chunks that start a
        sequence, start a page and end mid-page, and one unused slot."""
        from paddle_tpu.ops.pallas.paged_attention import (
            ragged_paged_append_attend_raw,
            ragged_paged_append_attend_reference)
        rng = np.random.default_rng(3)
        kvh, g, d, page, n_pages, maxp = 2, 3, 128, 128, 16, 2
        q_len = np.array([1, 1, 20, 0, 37, 5], np.int32)
        kv_len = np.array([130, 127, 100, 0, 0, 128], np.int32)
        q_start = (np.cumsum(q_len) - q_len).astype(np.int32)
        n_desc, t = len(q_len), int(q_len.sum())
        tables = 1 + rng.permutation(n_pages - 1)[:n_desc * maxp] \
            .reshape(n_desc, maxp).astype(np.int32)   # page 0 is the pad
        q, kn, vn, pools, scales = _ragged_operands(
            rng, pool, t, kvh, g, d, (kvh, n_pages, page, d))
        positions = np.concatenate(
            [np.arange(kv, kv + ql) for kv, ql in zip(kv_len, q_len)])
        row_tables = np.repeat(tables, q_len, axis=0)
        # jitted like every caller of it: inside a program XLA turns
        # the row quantizer's ``absmax / 127`` into a multiply by the
        # reciprocal, so an eager reference differs in the last bit of
        # a few scales (and the int8 codes that sit on a half)
        want = jax.jit(ragged_paged_append_attend_reference)(
            q, *pools, kn, vn, jnp.asarray(positions),
            jnp.asarray(row_tables), *scales)
        with pltpu.force_tpu_interpret_mode():
            got = ragged_paged_append_attend_raw(
                q, *pools, kn, vn, jnp.asarray(q_start),
                jnp.asarray(q_len), jnp.asarray(kv_len),
                jnp.asarray(tables), *scales)
        blocks = np.asarray(got[0].astype(jnp.float32))
        assert not blocks[3].any()                 # the unused slot
        flat = np.concatenate([blocks[s, :n] for s, n in enumerate(q_len)])
        ref = np.asarray(want[0].astype(jnp.float32))
        # f32: accumulation order only; bf16 outputs: one rounding
        tol = 2e-5 if pool == "float32" else 2.0 ** -7
        np.testing.assert_allclose(flat, ref, rtol=tol, atol=tol)
        for a, b in zip(got[1:], want[1:]):        # pools (and scales)
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
    def test_ragged_append_attend_stacked_pools_at_a_layer(self, pool):
        """The stacked-pool form the engine's layer loop calls: pools
        (and scale pools) ``[L, KVH, ...]`` whole, the layer a
        scalar-prefetch operand.  At layer 2 of 3, passed as a traced
        scalar, it equals the per-layer form run on that layer's
        slice — attention blocks, pages and scales bit for bit — and
        every other layer's pages come back untouched."""
        from paddle_tpu.ops.pallas.paged_attention import \
            ragged_paged_append_attend_raw
        rng = np.random.default_rng(5)
        n_layers, at = 3, 2
        kvh, g, d, page, n_pages, maxp = 2, 3, 128, 128, 9, 2
        q_len = np.array([1, 9, 0, 1], np.int32)
        kv_len = np.array([130, 0, 0, 127], np.int32)
        q_start = (np.cumsum(q_len) - q_len).astype(np.int32)
        n_desc, t = len(q_len), int(q_len.sum())
        tables = 1 + rng.permutation(n_pages - 1)[:n_desc * maxp] \
            .reshape(n_desc, maxp).astype(np.int32)
        q, kn, vn, pools, scales = _ragged_operands(
            rng, pool, t, kvh, g, d, (n_layers, kvh, n_pages, page, d))
        desc = (jnp.asarray(q_start), jnp.asarray(q_len),
                jnp.asarray(kv_len), jnp.asarray(tables))
        with pltpu.force_tpu_interpret_mode():
            want = ragged_paged_append_attend_raw(
                q, *(p[at] for p in pools), kn, vn, *desc,
                *(s[at] for s in scales))
            got = jax.jit(
                lambda layer: ragged_paged_append_attend_raw(
                    q, *pools, kn, vn, *desc, *scales, layer=layer))(
                jnp.int32(at))
        np.testing.assert_array_equal(
            np.asarray(got[0].astype(jnp.float32)),
            np.asarray(want[0].astype(jnp.float32)))
        for a, b, was in zip(got[1:], want[1:], pools + scales):
            assert a.shape == was.shape and a.dtype == was.dtype
            np.testing.assert_array_equal(np.asarray(a[at]),
                                          np.asarray(b))
            assert (np.asarray(a[at]) != np.asarray(was[at])).any()
            for other in range(n_layers):
                if other != at:
                    np.testing.assert_array_equal(
                        np.asarray(a[other]), np.asarray(was[other]))

    def test_paged_write_places_token(self):
        rng = np.random.default_rng(1)
        k_pages, v_pages = _rand_pages(rng)
        table = jnp.asarray(np.array([[3, 5, 0, 0]], np.int32))
        lens = jnp.asarray(np.array([9], np.int32))   # next pos 9: page 5 slot 1
        k_new = jnp.asarray(rng.normal(size=(1, 2, 16)).astype(np.float32))
        v_new = jnp.asarray(rng.normal(size=(1, 2, 16)).astype(np.float32))
        k2, v2 = paged_write(k_pages, v_pages, k_new, v_new, table, lens)
        np.testing.assert_array_equal(np.asarray(k2[:, 5, 1]),
                                      np.asarray(k_new[0]))
        np.testing.assert_array_equal(np.asarray(v2[:, 5, 1]),
                                      np.asarray(v_new[0]))
        # untouched elsewhere
        np.testing.assert_array_equal(np.asarray(k2[:, 3]),
                                      np.asarray(k_pages[:, 3]))


class TestPagedKVCache:
    def test_alloc_extend_release(self):
        c = PagedKVCache(n_pages=8, page_size=4, n_kv_heads=2, head_dim=8,
                         max_seqs=4, max_len=16)
        s0 = c.allocate(6)      # 2 pages
        s1 = c.allocate(3)      # 1 page
        assert c.free_page_count() == 7 - 3   # page 0 reserved
        c.advance(s0, 6)
        c.extend(s0, 3)         # needs a 3rd page
        assert c.free_page_count() == 3
        c.release(s0)
        assert c.free_page_count() == 6
        s2 = c.allocate(12)     # reuses freed pages
        assert c.free_page_count() == 3
        c.release(s1), c.release(s2)
        assert c.free_page_count() == 7

    def test_prefill_append_attend_matches_dense_cache(self):
        rng = np.random.default_rng(2)
        kvh, d, g = 2, 16, 2
        c = PagedKVCache(n_pages=32, page_size=8, n_kv_heads=kvh,
                         head_dim=d, max_seqs=4, max_len=64)
        pre = rng.normal(size=(11, kvh, d)).astype(np.float32)
        prev = rng.normal(size=(11, kvh, d)).astype(np.float32)
        slot = c.allocate(11)
        c.write_prefill(slot, pre, prev)
        # append two decode tokens
        for t in range(2):
            kn = rng.normal(size=(1, kvh, d)).astype(np.float32)
            vn = rng.normal(size=(1, kvh, d)).astype(np.float32)
            c.append(np.array([slot]), kn, vn)
            pre = np.concatenate([pre, kn], 0)
            prev = np.concatenate([prev, vn], 0)
        assert int(c.seq_lens[slot]) == 13
        q = rng.normal(size=(1, kvh * g, d)).astype(np.float32)
        got = np.asarray(c.attend(np.array([slot]), q, use_kernel=False))
        # dense oracle over the accumulated K/V
        k = np.swapaxes(pre, 0, 1)       # [KVH, L, D]
        v = np.swapaxes(prev, 0, 1)
        qh = q.reshape(kvh, g, d)
        s = np.einsum("kgd,kld->kgl", qh, k) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("kgl,kld->kgd", p, v).reshape(1, kvh * g, d)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


class TestPredictor:
    def test_save_then_serve(self, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        net.eval()
        from paddle_tpu.jit import save as jit_save
        from paddle_tpu.jit.to_static import InputSpec
        prefix = str(tmp_path / "inference")
        jit_save(net, prefix,
                 input_spec=[InputSpec([4, 8], "float32", "x")])

        cfg = Config(prefix)
        pred = create_predictor(cfg)
        x = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)

        # handle-style IO
        names = pred.get_input_names()
        pred.get_input_handle(names[0]).copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle(
            pred.get_output_names()[0]).copy_to_cpu()

        want = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, np.asarray(want), rtol=1e-5,
                                   atol=1e-5)

        # convenience run(inputs)
        out2 = pred.run([x])[0]
        np.testing.assert_allclose(out2, out, rtol=1e-6)

        # clone shares the compiled program but not the handles
        p2 = pred.clone()
        out3 = p2.run([x])[0]
        np.testing.assert_allclose(out3, out, rtol=1e-6)
