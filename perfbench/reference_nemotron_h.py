"""The benchmark's copy of the plain reference for the Nemotron-H family
(``paddle_tpu/models/references/nemotron_h.py`` is the program's; a test
holds the two to the same logits): the forward pass of a decoder whose
blocks are ONE of a Mamba-2 mixer, a softmax-attention mixer or a latent
sparse-expert layer, in ``jax.numpy``, float32, every matrix product
under ``jax.default_matmul_precision("highest")``.

``logits()`` computes it in BLOCKS so that it fits in the memory left
beside a serving engine at the published widths: one block's pieces
jitted one at a time, the held experts upcast ONE at a time (a traced
index, so all of them share one compiled program), the output head in
column blocks.  ``judge_served``, ``judge_recurrence``,
``judge_state_bits`` and ``judge_expert_layer`` are this configuration's
comparison that decides ``correct``.

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

# what the two hybrid configurations' judges share, limits apart: a bf16
# ulp, relative L2, the share of a state a bf16 cannot hold, rounding
# inside a compiled program (``reduce_precision``: the chip's compiler
# drops a down-and-up convert; PERF.md section 6, PR 28)
from perfbench.reference_qwen3_next import (bf16_ulp, not_bf16_share,
                                            rel_l2, round_to)

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


def recurrence(x, dt, a, b, c, d, state_dtype=None):
    """The Mamba-2 recurrence, token by token: x [S, nh, P]; dt [S, nh]
    (after softplus); a [nh] (negative); b, c [S, G, N]; d [nh];
    float32.  State [nh, P, N] float32, zero before position 0.
    ``state_dtype`` rounds the state to a lower precision after every
    token (the reading that has to come out as NOT correct).
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
        if state_dtype is not None:
            S = round_to(S, state_dtype)
        return S, jnp.einsum("hpn,hn->hp", S, ct) + d[:, None] * xt

    with jax.default_matmul_precision("highest"):
        s_end, y = jax.lax.scan(
            step, jnp.zeros(x.shape[1:] + b.shape[-1:], f32),
            (x, dt, b, c))
    return y, s_end


def ssm_mixer(h, lay, cfg, state_dtype=None):
    """Mamba-2 over one sequence h [S, H] -> [S, H].  ``state_dtype``:
    round the state to it after every token (the lower-precision
    reading; ``None`` keeps float32)."""
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
    y, _ = recurrence(x, delta, a, b, c, lay["D"].astype(f32),
                      state_dtype=state_dtype)
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


# -- the forward pass, in blocks -------------------------------------------------

HEAD_CHUNK = 16384            # most columns of the output head upcast at once
_FNS = None
_NUMBERS = ("layer_norm_epsilon", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")


def _fns():
    """The jitted pieces, built on first use (importing this module
    touches no backend).  ``cfg`` travels as a hashable tuple of its
    numbers, ``route_kw`` as one of ``route``'s control readings."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mixer(x, lay, cfg_items, kind, state_dtype):
        cfg = dict(cfg_items)
        h = rms(x, lay["in_norm"], float(cfg["layer_norm_epsilon"]))
        if kind == "full":
            return x + full_mixer(h, lay, cfg)
        return x + ssm_mixer(h, lay, cfg, state_dtype=state_dtype)

    def pre_moe(h, lay, cfg_items, route_kw):
        cfg = dict(cfg_items)
        kw = dict(route_kw)
        w = route(h, lay["router"], lay["router_bias"],
                  int(cfg["num_experts_per_tok"]),
                  bool(cfg["norm_topk_prob"]),
                  kw.pop("scale", float(cfg["routed_scaling_factor"])),
                  **kw)
        return w, h @ lay["latent_in"].astype(f32), shared_expert(h, lay)

    def expert_add(acc, v, w, e, lo, w1, w2):
        # ``e`` is traced: every held expert shares one compiled
        # program and only that expert's matrices are upcast
        col = jnp.take(w, lo + e, axis=1)[:, None]
        return acc + col * relu2_mlp(v, w1[e], w2[e])

    def out_of_latent(acc, w_up, shared):
        return acc @ w_up.astype(f32) + shared

    def norm(x, w, eps):
        return rms(x, w, eps)

    def head_chunk(h, w, c, *, width):
        return h @ jax.lax.dynamic_slice_in_dim(w, c, width, 1).astype(f32)

    jit = jax.jit
    return dict(
        mixer=jit(mixer, static_argnames=("cfg_items", "kind",
                                          "state_dtype")),
        pre_moe=jit(pre_moe, static_argnames=("cfg_items", "route_kw")),
        expert_add=jit(expert_add), out_of_latent=jit(out_of_latent),
        norm=jit(norm, static_argnames=("eps",)),
        head_chunk=jit(head_chunk, static_argnames=("width",)))


_MIXER_KEYS = {"ssm": ("in_norm", "in_proj", "conv", "conv_bias", "A_log",
                       "dt_bias", "D", "norm", "o"),
               "full": ("in_norm", "q", "k", "v", "o")}
_MOE_KEYS = ("router", "router_bias", "latent_in", "shared_up",
             "shared_down")


def _pieces():
    global _FNS
    if _FNS is None:
        _FNS = _fns()
    return _FNS


def moe_in_blocks(h, lay, cfg, shared=True, **route_kw):
    """One expert block's ``f(h)`` [S, H] for the normed rows ``h``
    (float32, on the device) under the configuration's own share, the
    held experts one at a time; without the shared expert's part when
    not ``shared``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    f = _pieces()
    items = tuple((k, cfg[k]) for k in _NUMBERS)
    lo, n_held = experts_held(cfg)
    with jax.default_matmul_precision("highest"):
        w, v, sh = f["pre_moe"](h, {k: lay[k] for k in _MOE_KEYS},
                                cfg_items=items,
                                route_kw=tuple(sorted(route_kw.items())))
        acc = jnp.zeros_like(v)
        for e in range(n_held):
            acc = f["expert_add"](acc, v, w, np.int32(e), np.int32(lo),
                                  lay["experts_up"], lay["experts_down"])
        return f["out_of_latent"](acc, lay["latent_out"],
                                  sh if shared else jnp.zeros_like(sh))


def logits(params: dict, cfg: dict, ids, state_dtype=None, **route_kw):
    """Teacher-forced logits [S, vocab] (float32, on the host) of one
    sequence of token ids under the canonical ``params``, given the
    configuration's own share (``experts_held``; the vocabulary slice
    is the one ``params`` hold).  ``state_dtype`` rounds the recurrent
    state to a lower precision after every token and ``route_kw`` is
    one of ``route``'s control readings — for the readings that have to
    come out as NOT correct (PERF.md), never for a check."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    f = _pieces()
    items = tuple((k, cfg[k]) for k in _NUMBERS)
    eps = float(cfg["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(jnp.float32)
        for kind, lay in zip(layer_kinds(cfg), params["layers"]):
            if kind == "ffn":
                x = x + moe_in_blocks(
                    f["norm"](x, lay["post_norm"], eps=eps), lay, cfg,
                    **route_kw)
            else:
                x = f["mixer"](x, {k: lay[k] for k in _MIXER_KEYS[kind]},
                               cfg_items=items, kind=kind,
                               state_dtype=state_dtype)
        h = f["norm"](x, params["final_norm"], eps=eps)
        head, cols = params["head"], []
        vocab = head.shape[1]
        n = next(n for n in range(1, vocab + 1)
                 if vocab % n == 0 and vocab // n <= HEAD_CHUNK)
        for i in range(n):
            cols.append(np.asarray(jax.device_get(f["head_chunk"](
                h, head, np.int32(i * (vocab // n)), width=vocab // n))))
    return np.concatenate(cols, axis=1)


# -- the comparison that decides ``correct`` -----------------------------------
#
# Four judges, each with a limit that lies between two readings (PERF.md
# section 4 gives both for each, with the seeds):
#
# 1. ``judge_served``: a served token must be the reference's argmax at
#    its position, or lie within TIE_ULPS bf16 ulps (8 significant bits)
#    of the reference's top logit there; the reference is teacher-forced
#    over the SERVED tokens, so the comparison goes on past a near-tie
#    instead of diverging with it.  Why a band at all: the program
#    computes in bf16 (weights, activations, the logits themselves, which
#    alone round by half an ulp) and the reference in float32, so with
#    seeded random weights the top two of 32,768 logits lie within the
#    program's rounding at some positions.
# 2. ``judge_recurrence``: the function the step programs call for the
#    Mamba-2 recurrence, handed one sequence the way the engine hands a
#    request over (prompt chunks of a page, several descriptors of one
#    slot in a launch, then one row a step), against ``recurrence`` above
#    on the same seeded float32 operands: relative L2 of the outputs and
#    of the final state.  It is what holds the state to float32 and the
#    products that touch it to full precision, and what a dropped decay
#    or a dropped ``D x`` fails by orders of magnitude.
# 3. ``judge_state_bits``: of the non-zero elements the served probes
#    left in the engine's state pools, the share that a bf16 cannot
#    represent: all but 2^-16 of them in a float32 state, none in a
#    state that is stored, or rounded after every token, in bf16.
# 4. ``judge_expert_layer``: the function the step programs call for the
#    expert layer (``moe_ffn``, grouped dispatch, the chip's kernels) on
#    seeded bf16 rows with the first expert block's own weights, against
#    ``moe_in_blocks`` on the same rows: relative L2 of the ROUTED part
#    alone (the shared expert's output is ten times larger at these
#    widths and would hide the router) and of the whole.  It is what a
#    dropped correction bias, a dropped route scale and a softmax router
#    fail; served tokens cannot tell them apart from rounding, because
#    the held share's routed part is a tenth of the block's output.
TIE_ULPS = 16
REC_REL_L2 = 2e-4
STATE_F32_SHARE = 0.5
EXPERT_REL_L2 = 0.05


def judge_served(ref_logits, prompt_len: int, served) -> dict:
    """Served token j was chosen after position ``prompt_len - 1 + j``
    of the teacher-forced sequence."""
    equal, gaps, bad = 0, [], []
    for j, tok in enumerate(served):
        row = ref_logits[prompt_len - 1 + j]
        top = float(row.max())
        if int(row.argmax()) == int(tok):
            equal += 1
            continue
        gap = (top - float(row[int(tok)])) / bf16_ulp(top)
        gaps.append(round(gap, 3))
        if gap > TIE_ULPS:
            bad.append({"position": j, "token": int(tok),
                        "ulps": round(gap, 3)})
    return {"positions": len(served), "equal": equal,
            "tie_gaps_ulps": gaps, "not_ties": bad,
            "max_gap_ulps": max(gaps, default=0.0), "ok": not bad}


def recurrence_inputs(cfg: dict, seed: int, n: int) -> tuple:
    """Seeded operands (x, dt, a, b, c, d) of ``n`` tokens at the
    configuration's geometry, distributed as the mixer makes them:
    x, B, C = silu(N(0, 1)); dt = softplus of a step size in
    ``[time_step_min, time_step_max]`` (log-uniform) plus N(0, 1) noise
    on the pre-activation; A = -exp U(log 1, log 16) and D ~ U(0.5,
    1.5) per head (as the configuration initialises them)."""
    import numpy as np
    r = np.random.default_rng(int(seed))
    nh, p, g, ns, _ = ssm_dims(cfg)
    f = np.float32

    def silu(v):
        return v / (1.0 + np.exp(-v))
    x = silu(r.normal(size=(n, nh, p))).astype(f)
    b = silu(r.normal(size=(n, g, ns))).astype(f)
    c = silu(r.normal(size=(n, g, ns))).astype(f)
    step = np.exp(r.uniform(math.log(float(cfg["time_step_min"])),
                            math.log(float(cfg["time_step_max"])),
                            size=nh))
    bias = step + np.log(-np.expm1(-step))
    dt = np.logaddexp(0.0, r.normal(size=(n, nh)) + bias[None, :]).astype(f)
    a = (-np.exp(r.uniform(0.0, math.log(16.0), size=nh))).astype(f)
    d = r.uniform(0.5, 1.5, size=nh).astype(f)
    return x, dt, a, b, c, d


def judge_recurrence(y_ref, s_ref, y, s) -> dict:
    out, state = rel_l2(y, y_ref), rel_l2(s, s_ref)
    return {"rel_l2_outputs": out, "rel_l2_state": state,
            "limit": REC_REL_L2,
            "ok": out <= REC_REL_L2 and state <= REC_REL_L2}


def judge_state_bits(state) -> dict:
    share = not_bf16_share(state)
    return {"not_bf16_share": share, "limit": STATE_F32_SHARE,
            "ok": share >= STATE_F32_SHARE}


def expert_rows(cfg: dict, seed: int, n: int):
    """Seeded rows [n, H] of rms 1 (what a norm hands the expert
    layer), float32."""
    import numpy as np
    h = np.random.default_rng(int(seed)).normal(
        size=(n, int(cfg["hidden_size"])))
    return (h / np.sqrt(np.mean(h * h, -1, keepdims=True))).astype(
        np.float32)


def judge_expert_layer(routed_ref, whole_ref, routed, whole) -> dict:
    r, w = rel_l2(routed, routed_ref), rel_l2(whole, whole_ref)
    return {"rel_l2_routed": r, "rel_l2_whole": w, "limit": EXPERT_REL_L2,
            "ok": r <= EXPERT_REL_L2 and w <= EXPERT_REL_L2}
