"""The plain reference for the Qwen3-Next family: the forward pass of a
hybrid Gated-DeltaNet / gated-attention sparse-expert decoder in
``jax.numpy``, float32, every matrix product under
``jax.default_matmul_precision("highest")``.

No kernels, no cache, no batching, no chunking: the linear layers run
their recurrence token by token.  It imports nothing of ``paddle_tpu``
and reads sizes from a dict with the published ``config.json`` key names
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).

The equations (``H`` hidden, eps ``rms_norm_eps``)
--------------------------------------------------
Layer ``i`` is **full** when ``(i + 1) % full_attention_interval == 0``,
else **linear**.  ``norm0(x) = x / rms(x) * (1 + w)`` — the weight is
zero-centred and the product is taken in float32.  Every layer::

    x = x + mixer(norm0(x));   x = x + moe(norm0(x))

and after the last layer a final ``norm0`` and an untied output head.

*Linear layer (Gated DeltaNet).*  ``Hk`` key heads, ``Hv`` value heads
(``Hv / Hk`` value heads share a key head: value head ``j`` reads key
head ``j // (Hv / Hk)``), head sizes ``dk``, ``dv``::

    q, k, v, z = split(x W_qkvz)          # Hk·dk, Hk·dk, Hv·dv, Hv·dv
    b, a       = split(x W_ba)            # Hv, Hv
    [q; k; v] <- silu(causal depthwise conv, kernel 4, no bias)
    beta = sigmoid(b);   g = -exp(A_log) * softplus(a + dt_bias)
    q, k <- l2norm(q), l2norm(k)  per head (eps 1e-6);   q <- q / sqrt(dk)
    per value head, state S [dk, dv] float32, zero before position 0:
        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
        o_t = S^T q_t
    y = (w_n * o / rms(o)) * silu(z)      # per head over dv, w_n plain
    out = y W_o

*Full layer (gated attention).*  ``nh`` query heads, ``kvh`` KV heads,
head ``hd``::

    q, gate = split(x W_q) per head (hd + hd);  k = x W_k;  v = x W_v
    q <- norm0(q), k <- norm0(k)  per head over hd
    rotary on the first ``hd * partial_rotary_factor`` dims (half
    rotation, theta ``rope_theta``);  causal softmax attention, scale
    1/sqrt(hd);   out = (attn * sigmoid(gate)) W_o

*Sparse experts.*  ``p = softmax(x W_r)`` over the PUBLISHED number of
experts; top-k; renormalised over the k kept (``norm_topk_prob``);
experts SwiGLU; plus ``sigmoid(x w_sg) * SwiGLU_shared(x)``.

The chip's share
----------------
``experts_held = (lo, n)``: the router keeps its published width and its
k, the renormalisation runs over all k, and only experts ``lo <= e <
lo + n`` add to the result (``params`` hold those ``n`` experts'
matrices, expert ``e`` at row ``e - lo``).  What the absent experts would
have added is left out and that partial result goes on to the next
layer.  ``vocab = (lo, n)``: ids, embedding rows and head columns are
those of the slice (``params`` may hold the slice already).

Departures from the published model
-----------------------------------
- The multi-token-prediction module is left out: the config has no key
  for it and it is no part of the served forward pass.
- ``W_qkvz`` / ``W_ba`` are laid out in plain blocks ``[q | k | v | z]``
  and ``[b | a]``; HF interleaves them per key-head group, a permutation
  of columns (weights here are seeded, no checkpoint is converted).
"""
from __future__ import annotations

import math

L2_EPS = 1e-6


def layer_kinds(cfg: dict) -> tuple:
    n, every = int(cfg["num_hidden_layers"]), \
        int(cfg["full_attention_interval"])
    return tuple("full" if (i + 1) % every == 0 else "linear"
                 for i in range(n))


def router_width(cfg: dict) -> int:
    """The published number of experts (the router's width), wherever
    the file holds it: ``published.num_experts`` when ``num_experts`` is
    the number held here."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def experts_held(cfg: dict) -> tuple:
    lo, hi = cfg.get("experts_held", (0, router_width(cfg)))
    return int(lo), int(hi) - int(lo)


def layer_params(sd: dict, i: int, kind: str) -> dict:
    """Layer ``i``'s weights out of the served model's flat state dict
    (``Qwen3NextForCausalLM``'s parameter names), under this file's
    short names.  Every matrix is [in, out]; nothing is copied or cast."""
    p = f"layers.{i}."
    lay = {"in_norm": sd[p + "input_layernorm.weight"],
           "post_norm": sd[p + "post_attention_layernorm.weight"]}
    if kind == "linear":
        m = p + "linear_attn."
        lay.update(qkvz=sd[m + "in_proj_qkvz.weight"],
                   ba=sd[m + "in_proj_ba.weight"], conv=sd[m + "conv_w"],
                   A_log=sd[m + "A_log"], dt_bias=sd[m + "dt_bias"],
                   norm=sd[m + "norm_w"], o=sd[m + "out_proj.weight"])
    else:
        m = p + "self_attn."
        lay.update(q=sd[m + "q_proj.weight"], k=sd[m + "k_proj.weight"],
                   v=sd[m + "v_proj.weight"], o=sd[m + "o_proj.weight"],
                   q_norm=sd[m + "q_norm.weight"],
                   k_norm=sd[m + "k_norm.weight"])
    m = p + "mlp."
    lay.update(router=sd[m + "gate.weight"],
               experts_gate=sd[m + "experts.gate_w"],
               experts_up=sd[m + "experts.up_w"],
               experts_down=sd[m + "experts.down_w"],
               shared_gate=sd[m + "shared_gate.weight"],
               shared_up=sd[m + "shared_up.weight"],
               shared_down=sd[m + "shared_down.weight"],
               shared_expert_gate=sd[m + "shared_expert_gate.weight"])
    return lay


def canonical(sd: dict, cfg: dict) -> dict:
    """The served model's flat state dict -> this reference's layout."""
    return {"embed": sd["embed_tokens.weight"],
            "final_norm": sd["norm.weight"], "head": sd["lm_head.weight"],
            "layers": [layer_params(sd, i, kind)
                       for i, kind in enumerate(layer_kinds(cfg))]}


# -- pieces ---------------------------------------------------------------------

def norm0(x, w, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def l2norm(x):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def rope_partial(x, theta, rot):
    """x [S, heads, D]: half rotation over the first ``rot`` dims."""
    import jax.numpy as jnp
    s = x.shape[0]
    f32 = jnp.float32
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=f32) / rot))
    ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr, xp], -1)


def linear_mixer(h, lay, cfg):
    """Gated DeltaNet over one sequence h [S, H] -> [S, H]."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    hk, hv = int(cfg["linear_num_key_heads"]), \
        int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), \
        int(cfg["linear_value_head_dim"])
    kw = int(cfg["linear_conv_kernel_dim"])
    eps = float(cfg["rms_norm_eps"])
    qkvz = h @ lay["qkvz"].astype(f32)
    ba = h @ lay["ba"].astype(f32)
    nk, nv = hk * dk, hv * dv
    mixed, z = qkvz[:, :2 * nk + nv], qkvz[:, 2 * nk + nv:]
    b, a = ba[:, :hv], ba[:, hv:]
    # causal depthwise conv: y_t = sum_j w[j] x_{t - (kw-1) + j}
    cw = lay["conv"].astype(f32)                          # [kw, C]
    xp = jnp.concatenate([jnp.zeros((kw - 1, mixed.shape[1]), f32),
                          mixed], 0)
    mixed = sum(xp[j:j + s] * cw[j][None, :] for j in range(kw))
    mixed = jax.nn.silu(mixed)
    q = mixed[:, :nk].reshape(s, hk, dk)
    k = mixed[:, nk:2 * nk].reshape(s, hk, dk)
    v = mixed[:, 2 * nk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)                              # [S, Hv]
    g = -jnp.exp(lay["A_log"].astype(f32))[None, :] * jax.nn.softplus(
        a + lay["dt_bias"].astype(f32)[None, :])          # [S, Hv]
    q = l2norm(q) / math.sqrt(dk)
    k = l2norm(k)
    rep = hv // hk
    q = jnp.repeat(q, rep, axis=1)                        # [S, Hv, dk]
    k = jnp.repeat(k, rep, axis=1)

    def step(S, xs):                     # S [Hv, dk, dv], one token
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), f32),
                        (q, k, v, g, beta))               # [S, Hv, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * lay["norm"].astype(f32)
    y = o * jax.nn.silu(z.reshape(s, hv, dv))
    return y.reshape(s, nv) @ lay["o"].astype(f32)


def full_mixer(h, lay, cfg):
    """Gated softmax attention over one sequence h [S, H] -> [S, H]."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    nh, kvh = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    rot = int(hd * float(cfg["partial_rotary_factor"]))
    qg = (h @ lay["q"].astype(f32)).reshape(s, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ lay["k"].astype(f32)).reshape(s, kvh, hd)
    v = (h @ lay["v"].astype(f32)).reshape(s, kvh, hd)
    q = norm0(q, lay["q_norm"], eps)
    k = norm0(k, lay["k_norm"], eps)
    q, k = rope_partial(q, theta, rot), rope_partial(k, theta, rot)
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    o = o * jax.nn.sigmoid(gate)
    return o.reshape(s, nh * hd) @ lay["o"].astype(f32)


def swiglu(h, wg, wu, wd):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return (jax.nn.silu(h @ wg.astype(f32)) * (h @ wu.astype(f32))) \
        @ wd.astype(f32)


def route(h, wr, k, norm_topk):
    """Combine weights [S, E_published]: top-k of the softmax, zero
    elsewhere, renormalised over the k kept when ``norm_topk``."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(h @ wr.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(p, k)
    if norm_topk:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    return jnp.zeros_like(p).at[
        jnp.arange(p.shape[0])[:, None], idx].set(vals)


def shared_expert(h, lay):
    import jax
    import jax.numpy as jnp
    y = swiglu(h, lay["shared_gate"], lay["shared_up"],
               lay["shared_down"])
    return y * jax.nn.sigmoid(h @ lay["shared_expert_gate"].astype(
        jnp.float32))


def moe(h, lay, cfg, held=None, shared=True):
    """The sparse-expert layer's part that experts ``held = (lo, n)``
    give (all of them when ``None``), plus the shared expert when
    ``shared``.  ``lay['experts_*']`` hold the ``n`` held experts."""
    lo, n = held if held is not None else (0, lay["experts_gate"].shape[0])
    w = route(h, lay["router"], int(cfg["num_experts_per_tok"]),
              bool(cfg["norm_topk_prob"]))
    y = shared_expert(h, lay) if shared else 0.0
    for e in range(n):
        y = y + w[:, lo + e][:, None] * swiglu(
            h, lay["experts_gate"][e], lay["experts_up"][e],
            lay["experts_down"][e])
    return y


# -- the forward pass -----------------------------------------------------------

def forward(params: dict, cfg: dict, ids, experts_held=None, vocab=None):
    """Teacher-forced logits [S, vocab] (float32) of one sequence of
    token ids.  ``experts_held = (lo, n)`` and ``vocab = (lo, n)`` give
    the chip's share (module docstring); ids count from the slice's
    first row."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    embed, head = params["embed"], params["head"]
    if vocab is not None and embed.shape[0] != vocab[1]:
        embed = embed[vocab[0]:vocab[0] + vocab[1]]
        head = head[:, vocab[0]:vocab[0] + vocab[1]]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(embed, jnp.asarray(ids, jnp.int32),
                     axis=0).astype(f32)
        for kind, lay in zip(layer_kinds(cfg), params["layers"]):
            h = norm0(x, lay["in_norm"], eps)
            x = x + (full_mixer if kind == "full"
                     else linear_mixer)(h, lay, cfg)
            h = norm0(x, lay["post_norm"], eps)
            x = x + moe(h, lay, cfg, experts_held)
        h = norm0(x, params["final_norm"], eps)
        return h @ head.astype(f32)
