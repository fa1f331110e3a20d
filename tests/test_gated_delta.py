"""The ragged Gated-DeltaNet mirror and the causal conv step against a
token-by-token recurrence, over a mixed batch of decode rows and prefill
chunks (several descriptors of one slot in one call, a fresh slot, a
slot with history, dead rows and dead descriptors)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.gated_delta import (causal_conv_step,
                                               gated_delta_chunk,
                                               ragged_gated_delta_reference)

HV, DK, DV, PAGE, N_SLOTS = 4, 8, 8, 16, 6


def _step(S, q, k, v, g, b):
    S = S * np.exp(g)[:, None, None]
    d = b[:, None] * (v - np.einsum("hkv,hk->hv", S, k))
    S = S + k[:, :, None] * d[:, None, :]
    return S, np.einsum("hkv,hk->hv", S, q)


def _inputs(t, seed=0):
    r = np.random.default_rng(seed)
    q = r.normal(size=(t, HV, DK)).astype(np.float32)
    k = r.normal(size=(t, HV, DK)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(t, HV, DV)).astype(np.float32)
    g = -np.abs(r.normal(size=(t, HV))).astype(np.float32)
    b = r.uniform(size=(t, HV)).astype(np.float32)
    return q, k, v, g, b


# (q_start, q_len, kv_len, slot): three decode rows, a fresh prompt in
# two chunks (page 16, then 10 rows), a 1-row chunk then its
# continuation (one slot twice with q_len 1 then 5), a chunk with history
DESCS = [(0, 1, 7, 0), (1, 1, 3, 1), (2, 1, 20, 2),
         (3, 16, 0, 3), (19, 10, 16, 3),
         (29, 1, 15, 4), (30, 5, 16, 4),
         (35, 11, 5, 5), (0, 0, 0, N_SLOTS)]
T = 50


def test_chunk_equals_the_recurrence():
    q, k, v, g, b = _inputs(64, 1)
    S0 = np.random.default_rng(2).normal(size=(HV, DK, DV)).astype(
        np.float32)
    o, s1 = gated_delta_chunk(*map(jnp.asarray, (q, k, v, g, b, S0)))
    S, want = S0, []
    for i in range(64):
        S, oi = _step(S, q[i], k[i], v[i], g[i], b[i])
        want.append(oi)
    np.testing.assert_allclose(o, np.stack(want), atol=2e-5)
    np.testing.assert_allclose(s1, S, atol=2e-5)


def test_ragged_mirror_equals_the_recurrence_per_slot():
    q, k, v, g, b = _inputs(T)
    state = np.random.default_rng(3).normal(
        size=(N_SLOTS + 1, HV, DK, DV)).astype(np.float32)
    state[N_SLOTS] = 0
    d = np.array(DESCS + [(0, 0, 0, N_SLOTS)] * 4, np.int32)
    o, new = jax.jit(ragged_gated_delta_reference,
                     static_argnames="page_size")(
        *map(jnp.asarray, (q, k, v, g, b, state)),
        *(jnp.asarray(d[:, i]) for i in range(4)), page_size=PAGE)
    want_state = state.copy()
    want_o = np.zeros((T, HV, DV), np.float32)
    for qs, ql, kl, sl in DESCS:
        if ql == 0:
            continue
        S = np.zeros_like(state[0]) if kl == 0 else want_state[sl]
        for r in range(qs, qs + ql):
            S, want_o[r] = _step(S, q[r], k[r], v[r], g[r], b[r])
        want_state[sl] = S
    live = np.zeros(T, bool)
    for qs, ql, _, _ in DESCS:
        live[qs:qs + ql] = True
    np.testing.assert_allclose(np.asarray(o)[live], want_o[live],
                               atol=3e-5)
    np.testing.assert_allclose(new, want_state, atol=3e-5)


def test_conv_step_carries_the_window_across_steps_and_descriptors():
    r = np.random.default_rng(5)
    c, kk = 6, 4
    w = r.normal(size=(kk, c)).astype(np.float32)
    seqs = {s: r.normal(size=(40, c)).astype(np.float32)
            for s in range(N_SLOTS)}

    def full(s, n):                      # the plain causal conv
        x = np.concatenate([np.zeros((kk - 1, c), np.float32),
                            seqs[s][:n]])
        return sum(x[j:j + n] * w[j] for j in range(kk))

    # step 1: slot 0 two rows (fresh), slot 1 five rows (fresh); step 2:
    # slot 0 one row, slot 1 one row, slot 2 four rows (fresh) in two
    # descriptors of 1 + 3 rows
    plans = [[(0, 0, 2), (1, 0, 5)],
             [(0, 2, 1), (1, 5, 1), (2, 0, 4)]]
    state = jnp.asarray(r.normal(size=(N_SLOTS + 1, kk - 1, c)),
                        jnp.float32)           # garbage: fresh must zero it
    tcap = 10
    for plan in plans:
        x = np.zeros((tcap, c), np.float32)
        row_slot = np.full(tcap, N_SLOTS, np.int32)
        hist = np.zeros(tcap, np.int32)
        n_rows = np.zeros(N_SLOTS + 1, np.int32)
        fresh = np.zeros(N_SLOTS + 1, bool)
        cur, where = 0, {}
        for s, pos, n in plan:
            x[cur:cur + n] = seqs[s][pos:pos + n]
            row_slot[cur:cur + n] = s
            hist[cur:cur + n] = np.arange(n)
            n_rows[s], fresh[s] = n, pos == 0
            where[s] = (cur, pos, n)
            cur += n
        y, state = causal_conv_step(*map(jnp.asarray, (
            x, w)), state, *map(jnp.asarray, (row_slot, hist, n_rows,
                                              fresh)))
        for s, (cur, pos, n) in where.items():
            np.testing.assert_allclose(
                np.asarray(y)[cur:cur + n], full(s, pos + n)[pos:],
                atol=1e-5)
    # untouched slots keep their window
    assert np.asarray(state)[3:N_SLOTS].any()
