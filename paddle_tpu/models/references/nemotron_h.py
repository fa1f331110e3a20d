"""The plain reference for the Nemotron-H family: the forward pass of a
decoder whose blocks are ONE of a Mamba-2 mixer, a softmax-attention
mixer or a latent sparse-expert layer, in ``jax.numpy``, float32, every
matrix product under ``jax.default_matmul_precision("highest")``.

No kernels, no cache, no batching, no chunking: the Mamba-2 blocks run
their recurrence token by token.  It imports nothing of ``paddle_tpu``
and reads sizes from a dict with the published ``config.json`` key names
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json,
``model_type`` ``nemotron_h``).

The equations (``H`` hidden, eps ``layer_norm_epsilon``)
---------------------------------------------------------
``x_0 = embed[ids]``; block ``i`` with letter ``p_i`` of
``hybrid_override_pattern``::

    x_{i+1} = x_i + f_{p_i}(rms(x_i) * w_i)

(plain RMSNorm, the weight initialised 1), then a final RMSNorm and an
untied output head.

*``M``, Mamba-2 (SSD).*  ``nh = mamba_num_heads`` heads of ``P =
mamba_head_dim``, ``d_in = nh P``; ``G = n_groups`` groups of ``N =
ssm_state_size``; conv channels ``d_in + 2 G N``::

    [z | xBC | dt] = u W_in                    # d_in, d_in + 2GN, nh
    xBC = silu(causal depthwise conv(xBC; w [K, C]) + b)
    x, B, C = split(xBC)                       # [nh, P], [G, N], [G, N]
    delta = softplus(dt + dt_bias);   A = -exp(A_log)     # one a head
    per head h (group h // (nh / G)), state S [P, N] float32, zero
    before position 0:
        S <- exp(delta_t A) S + delta_t x_t (x) B_t
        y_t = S C_t + D x_t
    y <- y * silu(z);  RMSNorm over each group of d_in / G channels,
    weight [d_in];   out = y W_out

*``*``, attention.*  ``q = u W_q`` (``nh_a`` heads of ``hd``), ``k, v``
(``kvh`` heads), no bias, NO rotary and no other position signal;
causal ``softmax(q k^T / sqrt(hd)) v``; ``W_o``.

*``E``, latent expert layer.*  ``s = sigmoid(u W_r)`` in float32 over
the PUBLISHED number of experts; chosen = top-k of ``s + b`` (``b`` the
correction-bias buffer; ``n_group`` 1: no grouped selection); weights
``w = s[chosen]`` (without ``b``), ``w <- w / (sum w + 1e-20)``
(``norm_topk_prob``), ``w <- routed_scaling_factor w``; ``v = u W_dn``
(H -> ``moe_latent_size``); expert ``e``: ``relu(v W1_e)^2 W2_e``;
``routed = (sum_k w_k f_{e_k}(v)) W_up`` (latent -> H); shared expert
``relu(u Ws1)^2 Ws2`` on the full width; output ``routed + shared``.

The chip's share
----------------
``experts_held = (lo, n)``: the router keeps its published width and its
k, the renormalisation runs over all k, and only experts ``lo <= e <
lo + n`` add to the latent sum BEFORE ``W_up`` (``params`` hold those
``n`` experts' matrices, expert ``e`` at row ``e - lo``); ``W_dn``,
``W_up``, the router and the shared expert are what every chip computes
alike.  ``vocab = (lo, n)``: ids, embedding rows and head columns are
those of the slice.

Departures from the published model
-----------------------------------
- The multi-token-prediction module (``num_nextn_predict_layers``) is
  no part of the served next-token forward pass and is left out.
- Weights are seeded, no checkpoint is converted: ``W_in`` is laid out
  in plain blocks ``[z | x | B | C | dt]``.
"""
from __future__ import annotations

import math

KINDS = {"M": "ssm", "*": "full", "E": "ffn"}


def layer_kinds(cfg: dict) -> tuple:
    """Per block: ``ssm`` (Mamba-2 mixer), ``full`` (attention mixer)
    or ``ffn`` (the expert layer, no mixer)."""
    pat = cfg["hybrid_override_pattern"]
    assert len(pat) == int(cfg["num_hidden_layers"]), (
        pat, cfg["num_hidden_layers"])
    return tuple(KINDS[p] for p in pat)


def router_width(cfg: dict) -> int:
    """The published number of routed experts (the router's width),
    wherever the file holds it: ``published.n_routed_experts`` when
    ``n_routed_experts`` is the number held here."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def experts_held(cfg: dict) -> tuple:
    lo, hi = cfg.get("experts_held", (0, router_width(cfg)))
    return int(lo), int(hi) - int(lo)


def ssm_dims(cfg: dict) -> tuple:
    """(heads, head size, groups, state size, conv channels)."""
    nh, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return nh, p, g, n, nh * p + 2 * g * n


def layer_params(sd: dict, i: int, kind: str) -> dict:
    """Block ``i``'s weights out of the served model's flat state dict
    (``NemotronHForCausalLM``'s parameter names), under this file's
    short names.  The block's one norm is ``in_norm`` in front of a
    mixer and ``post_norm`` in front of the expert layer (the names the
    serving engine's layer function reads).  Every matrix is [in, out];
    nothing is copied or cast."""
    p = f"layers.{i}."
    m = p + "mixer."
    if kind == "ssm":
        return {"in_norm": sd[p + "norm.weight"],
                "in_proj": sd[m + "in_proj.weight"],
                "conv": sd[m + "conv_w"], "conv_bias": sd[m + "conv_b"],
                "A_log": sd[m + "A_log"], "dt_bias": sd[m + "dt_bias"],
                "D": sd[m + "D"], "norm": sd[m + "norm_w"],
                "o": sd[m + "out_proj.weight"]}
    if kind == "full":
        return {"in_norm": sd[p + "norm.weight"],
                "q": sd[m + "q_proj.weight"], "k": sd[m + "k_proj.weight"],
                "v": sd[m + "v_proj.weight"], "o": sd[m + "o_proj.weight"]}
    return {"post_norm": sd[p + "norm.weight"],
            "router": sd[m + "gate.weight"],
            "router_bias": sd[m + "gate.e_score_correction_bias"],
            "latent_in": sd[m + "latent_in.weight"],
            "latent_out": sd[m + "latent_out.weight"],
            "experts_up": sd[m + "experts.up_w"],
            "experts_down": sd[m + "experts.down_w"],
            "shared_up": sd[m + "shared_up.weight"],
            "shared_down": sd[m + "shared_down.weight"]}


def canonical(sd: dict, cfg: dict) -> dict:
    """The served model's flat state dict -> this reference's layout."""
    return {"embed": sd["embed_tokens.weight"],
            "final_norm": sd["norm.weight"], "head": sd["lm_head.weight"],
            "layers": [layer_params(sd, i, kind)
                       for i, kind in enumerate(layer_kinds(cfg))]}


# -- pieces ---------------------------------------------------------------------

def rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def recurrence(x, dt, a, b, c, d):
    """The Mamba-2 recurrence, token by token: x [S, nh, P]; dt [S, nh]
    (after softplus); a [nh] (negative); b, c [S, G, N]; d [nh];
    float32.  State [nh, P, N] float32, zero before position 0.
    Returns (y [S, nh, P], the state after the last token)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    nh, g = x.shape[1], b.shape[1]
    rep = nh // g

    def step(S, xs):                      # S [nh, P, N], one token
        xt, dtt, bt, ct = xs
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        S = S * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, ct) + d[:, None] * xt

    with jax.default_matmul_precision("highest"):
        s_end, y = jax.lax.scan(
            step, jnp.zeros(x.shape[1:] + b.shape[-1:], f32),
            (x, dt, b, c))
    return y, s_end


def ssm_mixer(h, lay, cfg):
    """Mamba-2 over one sequence h [S, H] -> [S, H]."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    nh, p, g, n, cc = ssm_dims(cfg)
    d_in = nh * p
    kw = int(cfg["conv_kernel"])
    eps = float(cfg["layer_norm_epsilon"])
    zxd = h @ lay["in_proj"].astype(f32)
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + cc], zxd[:, d_in + cc:]
    # causal depthwise conv: y_t = sum_j w[j] x_{t - (kw-1) + j} + b
    cw = lay["conv"].astype(f32)                          # [kw, C]
    xp = jnp.concatenate([jnp.zeros((kw - 1, cc), f32), xbc], 0)
    xbc = sum(xp[j:j + s] * cw[j][None, :] for j in range(kw)) \
        + lay["conv_bias"].astype(f32)[None, :]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_in].reshape(s, nh, p)
    b = xbc[:, d_in:d_in + g * n].reshape(s, g, n)
    c = xbc[:, d_in + g * n:].reshape(s, g, n)
    delta = jax.nn.softplus(dt + lay["dt_bias"].astype(f32)[None, :])
    a = -jnp.exp(lay["A_log"].astype(f32))
    y, _ = recurrence(x, delta, a, b, c, lay["D"].astype(f32))
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    yg = y.reshape(s, g, d_in // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(s, d_in) * lay["norm"].astype(f32)[None, :]
    return y @ lay["o"].astype(f32)


def full_mixer(h, lay, cfg):
    """Causal softmax attention over one sequence, no position signal."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = h.shape[0]
    nh, kvh = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"])
    hd = int(cfg["head_dim"])
    q = (h @ lay["q"].astype(f32)).reshape(s, nh, hd)
    k = (h @ lay["k"].astype(f32)).reshape(s, kvh, hd)
    v = (h @ lay["v"].astype(f32)).reshape(s, kvh, hd)
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v)
    return o.reshape(s, nh * hd) @ lay["o"].astype(f32)


def relu2_mlp(h, w1, w2):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    a = jax.nn.relu(h @ w1.astype(f32))
    return (a * a) @ w2.astype(f32)


def route(h, wr, bias, k, norm_topk, scale, scoring="sigmoid",
          use_bias=True):
    """Combine weights [S, E_published]: sigmoid scores, top-k chosen by
    the bias-corrected scores, the weights the UNCORRECTED scores of
    the chosen, renormalised over the k and scaled; zero elsewhere.
    ``scoring="softmax"``, ``use_bias=False`` and ``scale=1`` are the
    controls' readings, never a check's."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = h @ wr.astype(f32)
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = s + bias.astype(f32)[None, :] if use_bias else s
    _, idx = jax.lax.top_k(pick, k)
    vals = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk:
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    vals = vals * scale
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], idx].set(vals)


def shared_expert(h, lay):
    return relu2_mlp(h, lay["shared_up"], lay["shared_down"])


def routed_latent(h, lay, cfg, held=None, **route_kw):
    """The latent sum ``sum_k w_k f_{e_k}(u W_dn)`` [S, latent] that
    experts ``held = (lo, n)`` give (all that ``lay`` holds when
    ``None``): what goes into ``W_up``."""
    import jax.numpy as jnp
    lo, n = held if held is not None else (0, lay["experts_up"].shape[0])
    w = route(h, lay["router"], lay["router_bias"],
              int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
              route_kw.pop("scale", float(cfg["routed_scaling_factor"])),
              **route_kw)
    v = h @ lay["latent_in"].astype(jnp.float32)
    acc = jnp.zeros_like(v)
    for e in range(n):
        acc = acc + w[:, lo + e][:, None] * relu2_mlp(
            v, lay["experts_up"][e], lay["experts_down"][e])
    return acc


def moe(h, lay, cfg, held=None, shared=True, **route_kw):
    """The expert layer's part that experts ``held`` give, through
    ``W_up``, plus the shared expert when ``shared``."""
    import jax.numpy as jnp
    y = routed_latent(h, lay, cfg, held, **route_kw) \
        @ lay["latent_out"].astype(jnp.float32)
    return y + shared_expert(h, lay) if shared else y


# -- the forward pass -----------------------------------------------------------

def forward(params: dict, cfg: dict, ids, experts_held=None, vocab=None,
            **route_kw):
    """Teacher-forced logits [S, vocab] (float32) of one sequence of
    token ids.  ``experts_held = (lo, n)`` and ``vocab = (lo, n)`` give
    the chip's share (module docstring); ids count from the slice's
    first row.  ``route_kw`` (``scale=``, ``scoring=``, ``use_bias=``)
    are ``route``'s control readings."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["layer_norm_epsilon"])
    embed, head = params["embed"], params["head"]
    if vocab is not None and embed.shape[0] != vocab[1]:
        embed = embed[vocab[0]:vocab[0] + vocab[1]]
        head = head[:, vocab[0]:vocab[0] + vocab[1]]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(embed, jnp.asarray(ids, jnp.int32),
                     axis=0).astype(f32)
        for kind, lay in zip(layer_kinds(cfg), params["layers"]):
            if kind == "ffn":
                x = x + moe(rms(x, lay["post_norm"], eps), lay, cfg,
                            experts_held, **route_kw)
            else:
                h = rms(x, lay["in_norm"], eps)
                x = x + (ssm_mixer if kind == "ssm"
                         else full_mixer)(h, lay, cfg)
        h = rms(x, params["final_norm"], eps)
        return h @ head.astype(f32)
