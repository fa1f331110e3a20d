"""Ragged Gated-DeltaNet: the linear-attention recurrence of a flat
mixed prefill+decode token batch, over the engine's step descriptors,
with a per-slot recurrent state read and written where it lies.

Per value head, state ``S`` [dk, dv] float32::

    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t = S^T q_t

``ragged_gated_delta_reference`` is the ``jax.numpy`` form the engine
runs (on the CPU always; on the chip until a Pallas kernel replaces it,
PERF.md §7): descriptor ``s`` covers flat rows ``[q_start, q_start +
q_len)`` of sequence slot ``slot[s]`` at context length ``kv_len[s]``.
A descriptor whose ``kv_len`` is 0 starts from a zero state (traced
data, no zeroing pass); several descriptors of one slot in one call hand
the state from one to the next in row order.

Two paths, both exact:

- single-row descriptors (every decode slot) update ALL slots' states in
  one dense pass: the rows scatter to their slots, one contraction reads
  ``S^T [k, q]``, one fused pass writes the new states;
- multi-row descriptors (prefill chunks, at most a page of rows) run a
  ``while_loop`` over just those descriptors, each in the chunked (WY)
  form over sub-chunks of ``SUB`` rows: the within-chunk dependence is
  a unit lower-triangular system ``(I + L) D = ...`` whose inverse is
  the finite product ``(I - L)(I + L^2)(I + L^4)...`` (``L`` is
  nilpotent), all matrix products — no token loop.

``causal_conv_step`` is the depthwise causal convolution in front of the
recurrence, over the same flat rows, with the per-slot window of the
last ``K - 1`` inputs carried beside the state.  ``gdn_inputs`` and
``gdn_output`` are the mixer's elementwise parts on either side of the
recurrence (gates and l2norm before it, the gated norm after it), shared
by the serving engine's layer function and the model's eager forward.
"""
from __future__ import annotations

import functools
import math

SUB = 64                     # rows of one WY sub-chunk


def _hi(fn):
    """Every contraction that touches the float32 state runs at full
    float32 precision: on a TPU the default would round the operands of
    an f32 product to bf16, which is the bf16 state the configuration
    does not have."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        import jax
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def gated_delta_chunk(q, k, v, g, beta, s0):
    """One WY chunk.  q, k [C, Hv, dk]; v [C, Hv, dv]; g, beta [C, Hv]
    (float32; a dead row has ``g = 0, beta = 0``); s0 [Hv, dk, dv].
    Returns (o [C, Hv, dv], s1)."""
    import jax.numpy as jnp
    c = q.shape[0]
    f32 = jnp.float32
    gc = jnp.cumsum(g, axis=0)                            # [C, Hv]
    i = jnp.arange(c)
    low = i[:, None] > i[None, :]                         # strict lower
    lowd = i[:, None] >= i[None, :]
    diff = gc[:, None, :] - gc[None, :, :]                # [C, C, Hv]
    decay = jnp.exp(jnp.where(lowd[:, :, None], diff, -jnp.inf))
    decay = jnp.transpose(decay, (2, 0, 1))               # [Hv, C, C]
    kk = jnp.einsum("ihd,jhd->hij", k, k)
    big_l = jnp.where(low[None], kk * decay, 0.0) \
        * jnp.transpose(beta)[:, :, None]                 # [Hv, C, C]
    # (I + L)^-1 = (I - L)(I + L^2)(I + L^4)... — L^C = 0
    x = -big_l
    tm = jnp.eye(c, dtype=f32)[None] + x
    p = x
    n = 1
    while 2 * n < c:
        p = jnp.einsum("hij,hjk->hik", p, p)
        tm = tm + jnp.einsum("hij,hjk->hik", tm, p)
        n *= 2
    gcum = jnp.exp(gc)                                    # [C, Hv]
    vb = v * beta[:, :, None]
    kbg = k * (beta * gcum)[:, :, None]
    u = jnp.einsum("hij,jhd->ihd", tm, vb)                # [C, Hv, dv]
    w = jnp.einsum("hij,jhd->ihd", tm, kbg)               # [C, Hv, dk]
    d = u - jnp.einsum("ihk,hkv->ihv", w, s0)
    qk = jnp.where(lowd[None],
                   jnp.einsum("ihd,jhd->hij", q, k) * decay, 0.0)
    o = jnp.einsum("ihk,hkv->ihv", q * gcum[:, :, None], s0) \
        + jnp.einsum("hij,jhv->ihv", qk, d)
    tail = jnp.exp(gc[-1][None, :] - gc)                  # [C, Hv]
    s1 = s0 * gcum[-1][:, None, None] \
        + jnp.einsum("ihk,ihv->hkv", k * tail[:, :, None], d)
    return o, s1


def _dense_mask(q_len, slot, n_slots):
    """Which descriptors the dense single-row pass takes: the leading
    run of ``q_len == 1`` descriptors, each the first of its slot."""
    import jax.numpy as jnp
    one = q_len == 1
    lead = jnp.cumsum(jnp.logical_not(one).astype(jnp.int32)) == 0
    s = q_len.shape[0]
    earlier = jnp.arange(s)[:, None] > jnp.arange(s)[None, :]
    dup = jnp.any(jnp.logical_and(
        earlier, jnp.logical_and(slot[:, None] == slot[None, :],
                                 (q_len > 0)[None, :])), axis=1)
    return one & lead & jnp.logical_not(dup) & (slot < n_slots)


@_hi
def ragged_gated_delta_reference(q, k, v, g, beta, state, q_start, q_len,
                                 kv_len, slot, *, page_size):
    """q, k [T, Hv, dk] (l2-normalised, q scaled; key heads already
    repeated to value heads); v [T, Hv, dv]; g, beta [T, Hv]; all
    float32.  state [n_slots + 1, Hv, dk, dv] float32, the last slot
    the pad slot.  q_start / q_len / kv_len / slot [S] int32
    descriptors (``q_len == 0`` unused, ``q_len <= page_size``).
    Returns (o [T, Hv, dv] float32, state')."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    t, hv, dk = q.shape
    dv = v.shape[-1]
    n_slots = state.shape[0] - 1
    s_max = q_start.shape[0]
    live = q_len > 0
    dense = _dense_mask(q_len, slot, n_slots)

    # -- single-row descriptors: one dense pass over every slot ---------------
    dslot = jnp.where(dense, slot, n_slots)               # others -> pad
    row = jnp.clip(q_start, 0, t - 1)

    def to_slots(x):
        return jnp.zeros((n_slots + 1,) + x.shape[1:], f32).at[
            dslot].set(x[row])
    active = jnp.zeros(n_slots + 1, bool).at[dslot].set(dense) \
        .at[n_slots].set(False)
    fresh = jnp.zeros(n_slots + 1, bool).at[dslot].set(
        dense & (kv_len == 0))
    qs, ks, vs = to_slots(q), to_slots(k), to_slots(v)
    gs, bs = jnp.exp(to_slots(g)), to_slots(beta)         # [N, Hv]
    s_in = jnp.where(fresh[:, None, None, None], 0.0, state)
    both = jnp.einsum("nhkv,nhkj->nhjv", s_in,
                      jnp.stack([ks, qs], axis=-1))       # [N, Hv, 2, dv]
    d = bs[..., None] * (vs - gs[..., None] * both[:, :, 0])
    o_slot = gs[..., None] * both[:, :, 1] \
        + jnp.sum(ks * qs, -1, keepdims=True) * d         # [N, Hv, dv]
    state = jnp.where(
        active[:, None, None, None],
        gs[..., None, None] * s_in + ks[..., :, None] * d[..., None, :],
        state)
    o = jnp.zeros((t + page_size, hv, dv), f32).at[
        jnp.where(dense, row, t + page_size - 1)].set(o_slot[dslot])

    # -- multi-row descriptors: a loop over just those -------------------------
    chunked = live & jnp.logical_not(dense)
    idx = jnp.arange(s_max)
    lo = jnp.min(jnp.where(chunked, idx, s_max))
    hi = jnp.max(jnp.where(chunked, idx + 1, 0))

    def pad(x):
        return jnp.concatenate(
            [x, jnp.zeros((page_size,) + x.shape[1:], x.dtype)], 0)
    qp, kp, vp, gp, bp = pad(q), pad(k), pad(v), pad(g), pad(beta)
    n_sub = -(-page_size // SUB)
    sub = min(SUB, page_size)

    def body(di, carry):
        state, o = carry
        ql = jnp.where(chunked[di], q_len[di], 0)
        sl = jnp.where(chunked[di], slot[di], n_slots)
        r0 = jnp.where(chunked[di], q_start[di], t)
        s_cur = jax.lax.dynamic_index_in_dim(state, sl, keepdims=False)
        s_cur = jnp.where((kv_len[di] == 0) & (ql > 0), 0.0, s_cur)
        for c in range(n_sub):
            def rows(x, c=c):
                return jax.lax.dynamic_slice_in_dim(x, r0 + c * sub, sub)
            alive = (c * sub + jnp.arange(sub)) < ql      # [sub]
            gm = jnp.where(alive[:, None], rows(gp), 0.0)
            bm = jnp.where(alive[:, None], rows(bp), 0.0)
            oc, s_cur = gated_delta_chunk(rows(qp), rows(kp), rows(vp),
                                          gm, bm, s_cur)
            o = jax.lax.dynamic_update_slice_in_dim(
                o, jnp.where(alive[:, None, None], oc, rows(o)),
                r0 + c * sub, 0)
        state = jax.lax.dynamic_update_index_in_dim(state, s_cur, sl, 0)
        return state, o

    state, o = jax.lax.fori_loop(lo, hi, body, (state, o))
    return o[:t], state


# What the step programs (and the benchmark's check of the recurrence)
# call: the ``jax.numpy`` form on every platform today; a Pallas kernel
# takes this name on the chip when there is one (PERF.md §7).
ragged_gated_delta = ragged_gated_delta_reference


def causal_conv_step(x, w, conv_state, row_slot, hist, n_rows, fresh):
    """The depthwise causal convolution over flat rows.

    x [T, C] float32 this step's inputs; w [K, C]; conv_state
    [n_slots + 1, K - 1, C] the last ``K - 1`` inputs of every slot
    before this step (pad slot last).  ``row_slot`` [T] each row's slot
    (dead rows: the pad slot); ``hist`` [T] how many of this step's
    rows of the same slot precede the row (they are contiguous);
    ``n_rows`` [n_slots + 1] live rows per slot this step; ``fresh``
    [n_slots + 1] slots whose sequence starts in this step (their window
    reads as zeros).  Returns (y [T, C] = sum_j w[j] x_{t-(K-1)+j},
    conv_state')."""
    import jax.numpy as jnp
    t, c = x.shape
    kk = w.shape[0]
    n_pad = conv_state.shape[0] - 1
    old = jnp.where(fresh[:, None, None], 0.0,
                    conv_state.astype(x.dtype))
    xp = jnp.concatenate([jnp.zeros((kk - 1, c), x.dtype), x], 0)
    y = x * w[kk - 1][None, :]
    for lag in range(1, kk):
        from_row = xp[kk - 1 - lag:kk - 1 - lag + t]      # row r - lag
        j = jnp.clip(kk - 1 - lag + hist, 0, kk - 2)
        from_state = old[row_slot, j]                     # [T, C]
        y = y + w[kk - 1 - lag][None, :] * jnp.where(
            (hist >= lag)[:, None], from_row, from_state)
    # the new window: the last K-1 of [old window; this step's rows]
    n = n_rows[:, None]                                   # [N, 1]
    j = jnp.arange(kk - 1)[None, :]
    shifted = jnp.take_along_axis(
        old, jnp.clip(j + n, 0, kk - 2)[:, :, None], axis=1)
    new = jnp.where((j + n <= kk - 2)[:, :, None], shifted, 0.0)
    rem = n_rows[row_slot] - 1 - hist                     # rows from end
    put = (rem < kk - 1) & (row_slot < n_pad)
    new = new.at[jnp.where(put, row_slot, n_pad),
                 jnp.clip(kk - 2 - rem, 0, kk - 2)].set(
        x, mode="drop")
    new = jnp.where((n_rows > 0)[:, None, None], new,
                    conv_state.astype(x.dtype))
    return y, new.astype(conv_state.dtype)


def gdn_inputs(mixed, b, a, a_log, dt_bias, c):
    """After the conv: silu, split, l2norm, the gates — the operands of
    the recurrence, float32, key heads repeated to value heads.
    mixed [T, C] f32; b, a [T, Hv]; ``c`` names the head geometry
    (``linear_num_key_heads`` ...: a model config or the engine's
    ``HybridArch``)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    t = mixed.shape[0]
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    nk = hk * dk
    mixed = jax.nn.silu(mixed.astype(f32))
    q = mixed[:, :nk].reshape(t, hk, dk)
    k = mixed[:, nk:2 * nk].reshape(t, hk, dk)
    v = mixed[:, 2 * nk:].reshape(t, hv, dv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q) / math.sqrt(dk), hv // hk, axis=1)
    k = jnp.repeat(l2(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(a_log.astype(f32))[None, :] * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32)[None, :])
    return q, k, v, g, beta


def gdn_output(o, z, norm_w, eps):
    """``(w_n * o / rms(o)) * silu(z)`` per head; o [T, Hv, dv] f32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * norm_w.astype(f32)
    return (o * jax.nn.silu(z.astype(f32).reshape(o.shape))).reshape(
        o.shape[0], -1)
