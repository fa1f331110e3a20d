"""Ragged Mamba-2 (SSD): the state-space recurrence of a flat mixed
prefill+decode token batch, over the engine's step descriptors, with a
per-slot recurrent state read and written where it lies.

Per head ``h`` (``nh`` heads of ``P`` channels; ``G`` groups of ``N``
state dims, head ``h`` reads group ``h // (nh / G)``), state ``S``
[P, N] float32::

    S <- exp(dt_t a) S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t

``ragged_ssd_reference`` is the ``jax.numpy`` form the engine runs (on
the CPU always; on the chip until a Pallas kernel replaces it, PERF.md
§7), over the same descriptors as ``gated_delta.ragged_gated_delta``:
descriptor ``s`` covers flat rows ``[q_start, q_start + q_len)`` of
sequence slot ``slot[s]`` at context length ``kv_len[s]``; ``kv_len ==
0`` starts from a zero state (traced data, no zeroing pass); several
descriptors of one slot in one call hand the state on in row order.

Two paths, both exact:

- single-row descriptors (every decode slot) update ALL slots' states in
  one dense elementwise pass — products and the ``S C`` sum on the
  vector unit in float32, no matrix unit, so no operand is rounded;
- multi-row descriptors (prefill chunks, at most a page of rows) run a
  ``fori_loop`` over just those descriptors, each in the chunked (SSD)
  form over sub-chunks of ``SUB`` rows: within a chunk ``y = (L o C B^T)
  (dt x)`` with ``L[i, j] = exp(sum_{j < r <= i} dt_r a)``, across it the
  state carried — all matrix products at full float32 precision, no
  token loop.

``ssd_inputs`` and ``ssd_output`` are the mixer's elementwise parts on
either side of the recurrence (activation, split and step sizes before
it, the gated group norm after it), shared by the serving engine's layer
function and the model's eager forward; the causal conv in front is
``gated_delta.causal_conv_step`` (plus this mixer's bias).
"""
from __future__ import annotations

from .gated_delta import _dense_mask, _hi

SUB = 128                    # rows of one SSD sub-chunk (``chunk_size``)


def ssd_chunk(x, dt, a, b, c, s0):
    """One SSD chunk.  x [C, nh, P]; dt [C, nh] (a dead row has ``dt =
    0``); a [nh]; b, c [C, G, N]; s0 [nh, P, N]; float32.  Returns
    (y [C, nh, P] without the ``D x`` term, s1)."""
    import jax.numpy as jnp
    n_rows, nh = dt.shape
    rep = nh // b.shape[1]
    cum = jnp.cumsum(dt * a[None, :], axis=0)             # [C, nh], <= 0
    i = jnp.arange(n_rows)
    lowd = i[:, None] >= i[None, :]
    diff = cum[:, None, :] - cum[None, :, :]              # [C, C, nh]
    decay = jnp.exp(jnp.where(lowd[:, :, None], diff, -jnp.inf))
    decay = jnp.transpose(decay, (2, 0, 1))               # [nh, C, C]
    cb = jnp.repeat(jnp.einsum("ign,jgn->gij", c, b), rep, axis=0)
    xdt = x * dt[:, :, None]
    ch = jnp.repeat(c, rep, axis=1)                       # [C, nh, N]
    bh = jnp.repeat(b, rep, axis=1)
    y = jnp.einsum("hij,jhp->ihp", cb * decay, xdt) \
        + jnp.einsum("ihn,hpn->ihp", ch * jnp.exp(cum)[:, :, None], s0)
    tail = jnp.exp(cum[-1][None, :] - cum)                # [C, nh]
    s1 = s0 * jnp.exp(cum[-1])[:, None, None] \
        + jnp.einsum("ihp,ihn->hpn", xdt * tail[:, :, None], bh)
    return y, s1


@_hi
def ragged_ssd_reference(x, dt, a, b, c, d, state, q_start, q_len, kv_len,
                         slot, *, page_size):
    """x [T, nh, P]; dt [T, nh] (after softplus); a [nh] (negative);
    b, c [T, G, N]; d [nh]; all float32.  state [n_slots + 1, nh, P, N]
    float32, the last slot the pad slot.  q_start / q_len / kv_len /
    slot [S] int32 descriptors (``q_len == 0`` unused, ``q_len <=
    page_size``).  Returns (y [T, nh, P] float32, state')."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    t, nh, p = x.shape
    rep = nh // b.shape[1]
    n_slots = state.shape[0] - 1
    s_max = q_start.shape[0]
    live = q_len > 0
    dense = _dense_mask(q_len, slot, n_slots)

    # -- single-row descriptors: one dense pass over every slot ---------------
    dslot = jnp.where(dense, slot, n_slots)               # others -> pad
    row = jnp.clip(q_start, 0, t - 1)

    def to_slots(v):
        return jnp.zeros((n_slots + 1,) + v.shape[1:], f32).at[
            dslot].set(v[row])
    active = jnp.zeros(n_slots + 1, bool).at[dslot].set(dense) \
        .at[n_slots].set(False)
    fresh = jnp.zeros(n_slots + 1, bool).at[dslot].set(
        dense & (kv_len == 0))
    xs, dts = to_slots(x), to_slots(dt)                   # [N, nh, P], [N, nh]
    bs = jnp.repeat(to_slots(b), rep, axis=1)             # [N, nh, N]
    cs = jnp.repeat(to_slots(c), rep, axis=1)
    s_in = jnp.where(fresh[:, None, None, None], 0.0, state)
    new = jnp.exp(dts * a[None, :])[..., None, None] * s_in \
        + (dts[..., None] * xs)[..., None] * bs[:, :, None, :]
    y_slot = jnp.sum(new * cs[:, :, None, :], axis=-1)    # [N, nh, P]
    state = jnp.where(active[:, None, None, None], new, state)
    y = jnp.zeros((t + page_size, nh, p), f32).at[
        jnp.where(dense, row, t + page_size - 1)].set(y_slot[dslot])

    # -- multi-row descriptors: a loop over just those -------------------------
    chunked = live & jnp.logical_not(dense)
    idx = jnp.arange(s_max)
    lo = jnp.min(jnp.where(chunked, idx, s_max))
    hi = jnp.max(jnp.where(chunked, idx + 1, 0))

    def pad(v):
        return jnp.concatenate(
            [v, jnp.zeros((page_size,) + v.shape[1:], v.dtype)], 0)
    xp, dtp, bp, cp = pad(x), pad(dt), pad(b), pad(c)
    n_sub = -(-page_size // SUB)
    sub = min(SUB, page_size)

    def body(di, carry):
        state, y = carry
        ql = jnp.where(chunked[di], q_len[di], 0)
        sl = jnp.where(chunked[di], slot[di], n_slots)
        r0 = jnp.where(chunked[di], q_start[di], t)
        s_cur = jax.lax.dynamic_index_in_dim(state, sl, keepdims=False)
        s_cur = jnp.where((kv_len[di] == 0) & (ql > 0), 0.0, s_cur)
        for ci in range(n_sub):
            def rows(v, ci=ci):
                return jax.lax.dynamic_slice_in_dim(v, r0 + ci * sub, sub)
            alive = (ci * sub + jnp.arange(sub)) < ql     # [sub]
            dtm = jnp.where(alive[:, None], rows(dtp), 0.0)
            yc, s_cur = ssd_chunk(rows(xp), dtm, a, rows(bp), rows(cp),
                                  s_cur)
            y = jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(alive[:, None, None], yc, rows(y)),
                r0 + ci * sub, 0)
        state = jax.lax.dynamic_update_index_in_dim(state, s_cur, sl, 0)
        return state, y

    state, y = jax.lax.fori_loop(lo, hi, body, (state, y))
    return y[:t] + d[None, :, None] * x, state


# What the step programs (and the benchmark's check of the recurrence)
# call: the ``jax.numpy`` form on every platform today; a Pallas kernel
# takes this name on the chip when there is one (PERF.md §7).
ragged_ssd = ragged_ssd_reference


def ssd_inputs(xbc, dt, a_log, dt_bias, c):
    """After the conv (and its bias): silu, split, the step sizes — the
    operands of the recurrence, float32.  xbc [T, C] f32; dt [T, nh];
    ``c`` names the geometry (``mamba_num_heads``, ``mamba_head_dim``,
    ``n_groups``, ``ssm_state_size``: a model config or the engine's
    ``HybridArch``).  Returns (x [T, nh, P], delta [T, nh], a [nh],
    b, c [T, G, N])."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    t = xbc.shape[0]
    nh, p = c.mamba_num_heads, c.mamba_head_dim
    g, n = c.n_groups, c.ssm_state_size
    d_in = nh * p
    xbc = jax.nn.silu(xbc.astype(f32))
    x = xbc[:, :d_in].reshape(t, nh, p)
    b = xbc[:, d_in:d_in + g * n].reshape(t, g, n)
    cc = xbc[:, d_in + g * n:].reshape(t, g, n)
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)[None, :])
    return x, delta, -jnp.exp(a_log.astype(f32)), b, cc


def ssd_output(y, z, norm_w, eps, groups):
    """``rms_group(y * silu(z)) * w``: y [T, nh, P] f32, z [T, nh P];
    the norm runs over each of ``groups`` groups of channels."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    t = y.shape[0]
    y = y.reshape(t, -1) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(t, groups, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    return yg.reshape(t, -1) * norm_w.astype(f32)[None, :]
