"""The ragged Mamba-2 (SSD) mirror against the token-by-token recurrence,
over a mixed batch of decode rows and prefill chunks (several
descriptors of one slot in one call, a fresh slot, a slot with history,
dead rows and dead descriptors), a state carried across chunk and page
boundaries and across a decode window, and the float32 state held."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.mamba2_ssd import (ragged_ssd_reference,
                                              ssd_chunk)

NH, P, G, N, PAGE, N_SLOTS = 8, 4, 2, 8, 16, 6


def _step(S, x, dt, a, b, c, d):
    """One token of one sequence: S [NH, P, N]."""
    bh, ch = np.repeat(b, NH // G, axis=0), np.repeat(c, NH // G, axis=0)
    S = S * np.exp(dt * a)[:, None, None] \
        + (dt[:, None] * x)[:, :, None] * bh[:, None, :]
    return S, np.einsum("hpn,hn->hp", S, ch) + d[:, None] * x


def _inputs(t, seed=0):
    r = np.random.default_rng(seed)
    f = np.float32
    x = r.normal(size=(t, NH, P)).astype(f)
    dt = np.logaddexp(0.0, r.normal(size=(t, NH)) - 2.0).astype(f)
    a = -r.uniform(1.0, 16.0, size=NH).astype(f)
    b = r.normal(size=(t, G, N)).astype(f)
    c = r.normal(size=(t, G, N)).astype(f)
    d = r.uniform(0.5, 1.5, size=NH).astype(f)
    return x, dt, a, b, c, d


# (q_start, q_len, kv_len, slot): three decode rows, a fresh prompt in
# two chunks (page 16, then 10 rows), a 1-row chunk then its
# continuation (one slot twice with q_len 1 then 5), a chunk with history
DESCS = [(0, 1, 7, 0), (1, 1, 3, 1), (2, 1, 20, 2),
         (3, 16, 0, 3), (19, 10, 16, 3),
         (29, 1, 15, 4), (30, 5, 16, 4),
         (35, 11, 5, 5), (0, 0, 0, N_SLOTS)]
T = 50
RAGGED = jax.jit(ragged_ssd_reference, static_argnames="page_size")


def _call(ops, state, descs, n_desc=13):
    d = np.array(list(descs) + [(0, 0, 0, N_SLOTS)] * (n_desc - len(descs)),
                 np.int32)
    return RAGGED(*map(jnp.asarray, ops), jnp.asarray(state),
                  *(jnp.asarray(d[:, i]) for i in range(4)),
                  page_size=PAGE)


def test_chunk_equals_the_recurrence():
    x, dt, a, b, c, d = _inputs(64, 1)
    S0 = np.random.default_rng(2).normal(size=(NH, P, N)).astype(
        np.float32)
    y, s1 = ssd_chunk(*map(jnp.asarray, (x, dt, a, b, c, S0)))
    S, want = S0, []
    for i in range(64):
        S, yi = _step(S, x[i], dt[i], a, b[i], c[i], np.zeros(NH))
        want.append(yi)
    np.testing.assert_allclose(y, np.stack(want), atol=3e-5)
    np.testing.assert_allclose(s1, S, atol=3e-5)


def test_ragged_mirror_equals_the_recurrence_per_slot():
    ops = _inputs(T)
    x, dt, a, b, c, d = ops
    state = np.random.default_rng(3).normal(
        size=(N_SLOTS + 1, NH, P, N)).astype(np.float32)
    state[N_SLOTS] = 0
    y, new = _call(ops, state, DESCS)
    want_state = state.copy()
    want_y = np.zeros((T, NH, P), np.float32)
    for qs, ql, kl, sl in DESCS:
        if ql == 0:
            continue
        S = np.zeros_like(state[0]) if kl == 0 else want_state[sl]
        for r in range(qs, qs + ql):
            S, want_y[r] = _step(S, x[r], dt[r], a, b[r], c[r], d)
        want_state[sl] = S
    live = np.zeros(T, bool)
    for qs, ql, _, _ in DESCS:
        live[qs:qs + ql] = True
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live],
                               atol=3e-5)
    np.testing.assert_allclose(new, want_state, atol=3e-5)


def test_a_state_carried_across_pages_chunks_and_a_decode_window():
    """One sequence of 70 tokens in slot 2 the way the engine hands it
    over: 48 rows a step as three page chunks in ONE call, then 16 + 6,
    then eight single-row calls (a decode window) — against the
    recurrence over all 78 tokens at once."""
    n = 78
    ops = _inputs(n, 4)
    x, dt, a, b, c, d = ops
    S, want = np.zeros((NH, P, N), np.float32), []
    for i in range(n):
        S, yi = _step(S, x[i], dt[i], a, b[i], c[i], d)
        want.append(yi)
    state = np.ones((N_SLOTS + 1, NH, P, N), np.float32)  # kv_len 0 resets
    got, pos = [], 0
    for rows in (48, 22) + (1,) * 8:
        step = [np.zeros((48,) + v.shape[1:], np.float32)
                for v in (x, dt, b, c)]
        for buf, v in zip(step, (x, dt, b, c)):
            buf[:rows] = v[pos:pos + rows]
        descs = [(r0, min(PAGE, rows - r0), pos + r0, 2)
                 for r0 in range(0, rows, PAGE)]
        y, state = _call((step[0], step[1], a, step[2], step[3], d),
                         state, descs)
        got.append(np.asarray(y)[:rows])
        pos += rows
    np.testing.assert_allclose(np.concatenate(got), np.stack(want),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(state)[2], S, atol=5e-5)
    # the other slots were never named: untouched
    assert (np.asarray(state)[[0, 1, 3, 4, 5]] == 1).all()


def test_the_state_stays_float32():
    """The pools' dtype survives, and what a call leaves in them is not
    representable in bf16 (a state stored or rounded in bf16 would
    be)."""
    ops = _inputs(T, 6)
    state = np.zeros((N_SLOTS + 1, NH, P, N), np.float32)
    _, new = _call(ops, state, DESCS)
    assert new.dtype == jnp.float32
    live = np.asarray(new)[:N_SLOTS]
    odd = live != np.asarray(
        jnp.asarray(live).astype(jnp.bfloat16).astype(jnp.float32))
    assert odd.sum() / max((live != 0).sum(), 1) > 0.9


@pytest.mark.parametrize("drop", ["decay", "D"])
def test_a_dropped_term_shows(drop):
    """The decay ``exp(dt a)`` and the skip ``D x`` each move the
    output far beyond the tolerance above."""
    ops = list(_inputs(T, 7))
    state = np.zeros((N_SLOTS + 1, NH, P, N), np.float32)
    y, _ = _call(ops, state, DESCS)
    if drop == "decay":
        ops[2] = np.zeros_like(ops[2])
    else:
        ops[5] = np.zeros_like(ops[5])
    y2, _ = _call(ops, state, DESCS)
    assert np.abs(np.asarray(y) - np.asarray(y2)).max() > 0.1
