"""The benchmark's copy of the plain reference for the Qwen3-Next family
(``paddle_tpu/models/references/qwen3_next.py`` is the program's; a test
holds the two to the same logits): the forward pass of a hybrid
Gated-DeltaNet / gated-attention sparse-expert decoder in ``jax.numpy``,
float32, every matrix product under
``jax.default_matmul_precision("highest")``.

``logits()`` computes it in BLOCKS so that it fits in the memory left
beside a serving engine at the published widths: one layer's pieces
jitted one at a time, the held experts upcast ONE at a time (a traced
index, so all of them share one compiled program), the output head in
column blocks.  ``judge_served`` is this configuration's comparison that
decides ``correct``.

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


def linear_mixer(h, lay, cfg, state_dtype=None):
    """Gated DeltaNet over one sequence h [S, H] -> [S, H].
    ``state_dtype``: round the state to it after every token (the
    lower-precision reading; ``None`` keeps float32)."""
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

    o, _ = recurrence(q, k, v, g, beta, state_dtype=state_dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * lay["norm"].astype(f32)
    y = o * jax.nn.silu(z.reshape(s, hv, dv))
    return y.reshape(s, nv) @ lay["o"].astype(f32)


def round_to(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s exponent and mantissa, still
    float32.  ``reduce_precision`` and not a pair of converts: inside a
    compiled program the chip's compiler may drop a down-and-up convert
    as excess precision, and the lower-precision reading then reads
    0.0 (my chip run, PR 28)."""
    import jax
    import jax.numpy as jnp
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def recurrence(q, k, v, g, beta, state_dtype=None):
    """The gated delta rule, token by token, per value head: q, k
    [S, Hv, dk]; v [S, Hv, dv]; g, beta [S, Hv]; float32.  State
    [Hv, dk, dv] float32, zero before position 0.  ``state_dtype``
    rounds the state to a lower precision after every token (the
    reading that has to come out as NOT correct).  Returns
    (o [S, Hv, dv], the state after the last token)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def step(S, xs):                     # S [Hv, dk, dv], one token
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        if state_dtype is not None:
            S = round_to(S, state_dtype)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    with jax.default_matmul_precision("highest"):
        s_end, o = jax.lax.scan(
            step, jnp.zeros(q.shape[1:] + v.shape[-1:], f32),
            (q, k, v, g, beta))
    return o, s_end


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


# -- the forward pass, in blocks -------------------------------------------------

HEAD_CHUNK = 16384            # most columns of the output head upcast at once
_FNS = None


def _fns():
    """The jitted pieces, built on first use (importing this module
    touches no backend).  ``cfg`` travels as a hashable tuple of its
    numbers."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mixer(x, lay, cfg_items, kind, state_dtype):
        cfg = dict(cfg_items)
        h = norm0(x, lay["in_norm"], float(cfg["rms_norm_eps"]))
        if kind == "full":
            return x + full_mixer(h, lay, cfg)
        return x + linear_mixer(h, lay, cfg, state_dtype=state_dtype)

    def pre_moe(x, lay, cfg_items):
        cfg = dict(cfg_items)
        h = norm0(x, lay["post_norm"], float(cfg["rms_norm_eps"]))
        w = route(h, lay["router"], int(cfg["num_experts_per_tok"]),
                  bool(cfg["norm_topk_prob"]))
        return h, w, shared_expert(h, lay)

    def expert_add(acc, h, w, e, lo, wg, wu, wd):
        # ``e`` is traced: every held expert shares one compiled
        # program and only that expert's matrices are upcast
        col = jnp.take(w, lo + e, axis=1)[:, None]
        return acc + col * swiglu(h, wg[e], wu[e], wd[e])

    def final(x, w, eps):
        return norm0(x, w, eps)

    def head_chunk(h, w, c, *, width):
        return h @ jax.lax.dynamic_slice_in_dim(w, c, width, 1).astype(f32)

    jit = jax.jit
    return dict(
        mixer=jit(mixer, static_argnames=("cfg_items", "kind",
                                          "state_dtype")),
        pre_moe=jit(pre_moe, static_argnames=("cfg_items",)),
        expert_add=jit(expert_add),
        final=jit(final, static_argnames=("eps",)),
        head_chunk=jit(head_chunk, static_argnames=("width",)))


_MIXER_KEYS = {"linear": ("in_norm", "qkvz", "ba", "conv", "A_log",
                          "dt_bias", "norm", "o"),
               "full": ("in_norm", "q", "k", "v", "o", "q_norm", "k_norm")}
_MOE_KEYS = ("post_norm", "router", "shared_gate", "shared_up",
             "shared_down", "shared_expert_gate")
_NUMBERS = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
            "head_dim", "partial_rotary_factor", "rope_theta",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "num_experts_per_tok",
            "norm_topk_prob")


def logits(params: dict, cfg: dict, ids, state_dtype=None):
    """Teacher-forced logits [S, vocab] (float32, on the host) of one
    sequence of token ids under the canonical ``params``, given the
    configuration's own share (``experts_held``; the vocabulary slice
    is the one ``params`` hold).  ``state_dtype`` rounds the recurrent
    state to a lower precision after every token — for the reading
    that has to come out as NOT correct (PERF.md), never for a check."""
    global _FNS
    import jax
    import jax.numpy as jnp
    import numpy as np
    if _FNS is None:
        _FNS = _fns()
    f = _FNS
    items = tuple((k, cfg[k]) for k in _NUMBERS)
    lo, n_held = experts_held(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(jnp.float32)
        for kind, lay in zip(layer_kinds(cfg), params["layers"]):
            x = f["mixer"](x, {k: lay[k] for k in _MIXER_KEYS[kind]},
                           cfg_items=items, kind=kind,
                           state_dtype=state_dtype)
            h, w, y = f["pre_moe"](x, {k: lay[k] for k in _MOE_KEYS},
                                   cfg_items=items)
            for e in range(n_held):
                y = f["expert_add"](
                    y, h, w, np.int32(e), np.int32(lo),
                    lay["experts_gate"], lay["experts_up"],
                    lay["experts_down"])
            x = x + y
        h = f["final"](x, params["final_norm"],
                       eps=float(cfg["rms_norm_eps"]))
        head, cols = params["head"], []
        vocab = head.shape[1]
        n = next(n for n in range(1, vocab + 1)
                 if vocab % n == 0 and vocab // n <= HEAD_CHUNK)
        for i in range(n):
            cols.append(np.asarray(jax.device_get(f["head_chunk"](
                h, head, np.int32(i * (vocab // n)), width=vocab // n))))
    return np.concatenate(cols, axis=1)


# -- the comparison that decides ``correct`` -----------------------------------

# A served token must be the reference's argmax at its position, or lie
# within TIE_ULPS bf16 ulps (8 significant bits) of the reference's top
# logit there; the reference is teacher-forced over the SERVED tokens,
# so the comparison goes on past a near-tie instead of diverging with
# it.  Why a band at all: the program computes in bf16 (weights,
# activations, the logits themselves, which alone round by half an ulp)
# and the reference in float32, so with seeded random weights the top
# two of 75,968 logits lie within the program's rounding at about one
# position in thirty.  The limit lies between two readings (PERF.md §4):
# the largest shortfall the program gave on the chip over its 45 runs,
# 5.19 ulps (22 of 624 probe positions over 13 seeds differed at all:
# six by more than 2, four by more than 3, two by more than 4 — a tail
# that falls by e every 1.6 ulps), and what a dropped decay ``exp(g)``
# gives: 332 ulps and 0 of 24 tokens equal through this judge on the
# chip, 330-344 ulps at 511 of 512 positions in the reference itself.
# 16 is 3.1 x the first (fresh seeds read higher: by that tail a run of
# 48 positions passes 8 once in a hundred runs and 16 once in ten
# thousand) and a twentieth of the second.  What it CANNOT tell apart:
# a recurrent state rounded to bf16 moves the logits by 1.0 % (relative
# L2) and no token by more than 1.96 ulps — inside the program's own
# rounding, which moves the logits by 2.2 % — so the state's precision
# has limits of its own, further down (``judge_recurrence``,
# ``judge_state_bits``).
TIE_ULPS = 16


def bf16_ulp(x: float) -> float:
    """Spacing of bf16 values in the binade of ``x``."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -100))) - 7)


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


# -- the recurrent state's precision -------------------------------------------
#
# The configuration states a float32 recurrent state.  Served tokens and
# logits cannot hold the program to it: computing in bf16 (weights,
# activations, residual stream) the SOUND program's logits lie 2.2 %
# (relative L2; 1.6-3.0 % at four probes' judged positions on the chip)
# from this float32 reference, the reference with its state rounded to
# bf16 after every token only 1.0 % (0.7 % there), and that rounding
# moves no token by more than 1.96 bf16 ulps (PERF.md §4: the rounding
# of the activations hides the state's two to four times over).  So the state is
# held to float32 where it can be seen:
#
# 1. ``judge_recurrence``: the function the step programs call for the
#    recurrence, handed one sequence the way the engine hands a request
#    over (prompt chunks of a page, several descriptors of one slot in
#    a launch, then one row a step), against ``recurrence`` above on the
#    same seeded float32 operands: relative L2 of the outputs and of the
#    final state.  REC_REL_L2 lies between the largest the program read
#    on the chip over 15 seeds, 1.8e-5 (4e-7 on most), and what
#    ``recurrence(state_dtype=bfloat16)`` reads there, 1.7e-3 (a pool
#    rounded to bf16 between steps reads the same in its state; the
#    chip's default matmul precision 3.3e-3 in its outputs): 11 x over
#    the first, 8 x under the second (PERF.md §4).
# 2. ``judge_state_bits``: of the non-zero elements the served probes
#    left in the engine's state pools, the share that a bf16 cannot
#    represent: all but 2^-16 of them in a float32 state, none in a
#    state that is stored, or rounded after every token, in bf16.
REC_REL_L2 = 2e-4
STATE_F32_SHARE = 0.5


def recurrence_inputs(cfg: dict, seed: int, n: int) -> tuple:
    """Seeded operands (q, k, v, g, beta) of ``n`` tokens at the
    configuration's head geometry, distributed as the mixer makes them:
    l2-normalised q (scaled by 1/sqrt(dk)) and k, key heads repeated to
    value heads; v ~ N(0, 1); beta = sigmoid(N(0, 1)); g = -A *
    softplus(N(0, 1) + 1) with A ~ U(0, 16) per value head (``A_log``
    and ``dt_bias`` as the configuration initialises them)."""
    import numpy as np
    r = np.random.default_rng(int(seed))
    hk, hv = int(cfg["linear_num_key_heads"]), \
        int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), \
        int(cfg["linear_value_head_dim"])
    f = np.float32

    def unit(x):
        return x / np.sqrt(np.sum(x * x, -1, keepdims=True) + L2_EPS)
    q = np.repeat(unit(r.normal(size=(n, hk, dk))) / math.sqrt(dk),
                  hv // hk, axis=1).astype(f)
    k = np.repeat(unit(r.normal(size=(n, hk, dk))), hv // hk,
                  axis=1).astype(f)
    v = r.normal(size=(n, hv, dv)).astype(f)
    beta = (1.0 / (1.0 + np.exp(-r.normal(size=(n, hv))))).astype(f)
    a = r.uniform(0.0, 16.0, size=hv)
    g = (-a[None, :] * np.logaddexp(0.0, r.normal(size=(n, hv)) + 1.0)
         ).astype(f)
    return q, k, v, g, beta


def rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def judge_recurrence(o_ref, s_ref, o, s) -> dict:
    out, state = rel_l2(o, o_ref), rel_l2(s, s_ref)
    return {"rel_l2_outputs": out, "rel_l2_state": state,
            "limit": REC_REL_L2,
            "ok": out <= REC_REL_L2 and state <= REC_REL_L2}


def not_bf16_share(state) -> float:
    """Share of the non-zero elements of ``state`` (an array or several)
    that a bf16 cannot represent."""
    import jax.numpy as jnp
    arrays = state if isinstance(state, (tuple, list)) else (state,)
    odd = live = 0
    for a in arrays:
        a = jnp.asarray(a, jnp.float32)
        live += int(jnp.sum(a != 0))
        odd += int(jnp.sum(a != a.astype(jnp.bfloat16).astype(
            jnp.float32)))
    return odd / max(live, 1)


def judge_state_bits(state) -> dict:
    share = not_bf16_share(state)
    return {"not_bf16_share": share, "limit": STATE_F32_SHARE,
            "ok": share >= STATE_F32_SHARE}
