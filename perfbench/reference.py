"""The plain reference: a decoder forward in float32 ``jax.numpy``.

RMSNorm, rotary embedding (half-rotation, the HF Llama convention),
causal softmax attention with grouped KV heads, SwiGLU; for a sparse
block a softmax router that keeps the top-k probabilities WITHOUT
renormalising them (DeepSeekMoE-16B: ``norm_topk_prob`` false) plus the
always-on shared expert.  No kernels, no cache, no batching, and every
matrix product under ``jax.default_matmul_precision("highest")``.

It reads the served model's own weights by their state-dict names
(``canonical`` maps the two families this repo's builders produce) and
upcasts one piece at a time: one projection, one expert, one slice of
the output head.  At DeepSeekMoE widths an expert is 35 MB in float32, so
the reference fits in the memory left beside a serving engine.

Departures from the published models, both the served program's and so
stated in the configuration files: every DeepSeekMoE layer is sparse (the
published first layer is dense), and the two shared experts are one
SwiGLU of twice the width (the same function).
"""
from __future__ import annotations

import math

HEAD_CHUNK = 16384            # most columns of the output head upcast at once


def canonical(arch: str, sd: dict, n_layers: int) -> dict:
    """State dict of the served model -> the reference's own layout.
    Every matrix is [in, out]."""
    if arch == "llama":
        pre, head = "llama.", "lm_head.weight"
    elif arch == "qwen2_moe":
        pre, head = "", "lm_head.weight"
    else:
        raise ValueError(f"reference knows no architecture {arch!r}")
    out = {"embed": sd[pre + "embed_tokens.weight"],
           "final_norm": sd[pre + "norm.weight"], "head": sd[head],
           "layers": []}
    for i in range(n_layers):
        p = f"{pre}layers.{i}."
        lay = {"in_norm": sd[p + "input_layernorm.weight"],
               "post_norm": sd[p + "post_attention_layernorm.weight"]}
        for k in "qkvo":
            lay[k] = sd[p + f"self_attn.{k}_proj.weight"]
        if arch == "llama":
            for k in ("gate", "up", "down"):
                lay[k] = sd[p + f"mlp.{k}_proj.weight"]
        else:
            lay["router"] = sd[p + "mlp.gate.weight"]
            for k in ("gate", "up", "down"):
                lay["experts_" + k] = sd[p + f"mlp.experts.{k}_w"]
                lay["shared_" + k] = sd[p + f"mlp.shared_{k}.weight"]
        out["layers"].append(lay)
    return out


def _fns():
    """The jitted pieces, built on first use (importing this module
    touches no backend)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def rms(x, w, eps):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(f32)

    def rope(x, theta):                       # x [S, heads, D]
        s, _, d = x.shape
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=f32) / d))
        ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def attention(x, nw, wq, wk, wv, wo, *, heads, kv_heads, eps, theta):
        s = x.shape[0]
        h = rms(x, nw, eps)
        q = (h @ wq.astype(f32)).reshape(s, heads, -1)
        k = (h @ wk.astype(f32)).reshape(s, kv_heads, -1)
        v = (h @ wv.astype(f32)).reshape(s, kv_heads, -1)
        q, k = rope(q, theta), rope(k, theta)
        g = heads // kv_heads
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
        mask = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
        return x + o @ wo.astype(f32)

    def swiglu(h, wg, wu, wd):
        a = h @ wg.astype(f32)
        return (jax.nn.silu(a) * (h @ wu.astype(f32))) @ wd.astype(f32)

    def route(h, wr, *, k):
        p = jax.nn.softmax(h @ wr.astype(f32), axis=-1)
        kth = jnp.sort(p, axis=-1)[:, -k][:, None]
        return jnp.where(p >= kth, p, 0.0)      # top-k kept, not renormed

    def expert_add(acc, h, w, e, wg, wu, wd):
        # ``e`` is traced, so 64 experts share one compiled program and
        # only the one expert's matrices are upcast
        return acc + jnp.take(w, e, axis=1)[:, None] * swiglu(
            h, wg[e], wu[e], wd[e])

    def head_chunk(h, w, c, *, width):
        return h @ jax.lax.dynamic_slice_in_dim(w, c, width, 1).astype(f32)

    jit = jax.jit
    return dict(
        rms=jit(rms, static_argnames=("eps",)),
        attention=jit(attention, static_argnames=(
            "heads", "kv_heads", "eps", "theta")),
        swiglu=jit(swiglu), route=jit(route, static_argnames=("k",)),
        expert_add=jit(expert_add),
        head_chunk=jit(head_chunk, static_argnames=("width",)))


_FNS = None


def logits(params: dict, cfg: dict, ids):
    """Teacher-forced logits [S, vocab] (float32, on the host) of one
    sequence of token ids under the canonical ``params``."""
    global _FNS
    import jax
    import jax.numpy as jnp
    import numpy as np
    if _FNS is None:
        _FNS = _fns()
    f = _FNS
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(jnp.float32)
        for lay in params["layers"]:
            x = f["attention"](
                x, lay["in_norm"], lay["q"], lay["k"], lay["v"], lay["o"],
                heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]), eps=eps,
                theta=theta)
            h = f["rms"](x, lay["post_norm"], eps=eps)
            if "router" in lay:
                w = f["route"](h, lay["router"],
                               k=int(cfg["num_experts_per_tok"]))
                y = f["swiglu"](h, lay["shared_gate"], lay["shared_up"],
                                lay["shared_down"])
                for e in range(lay["experts_gate"].shape[0]):
                    y = f["expert_add"](
                        y, h, w, np.int32(e), lay["experts_gate"],
                        lay["experts_up"], lay["experts_down"])
            else:
                y = f["swiglu"](h, lay["gate"], lay["up"], lay["down"])
            x = x + y
        h = f["rms"](x, params["final_norm"], eps=eps)
        head, cols = params["head"], []
        vocab = head.shape[1]
        n = next(n for n in range(1, vocab + 1)
                 if vocab % n == 0 and vocab // n <= HEAD_CHUNK)
        for i in range(n):
            cols.append(np.asarray(jax.device_get(f["head_chunk"](
                h, head, np.int32(i * (vocab // n)), width=vocab // n))))
    return np.concatenate(cols, axis=1)


# -- the comparison that decides ``correct`` -----------------------------------

TIE_ULPS = 2      # PR 21: nine bf16 ties seen on the chip, none above 2


def bf16_ulp(x: float) -> float:
    """Spacing of bf16 values in the binade of ``x`` (8 significant
    bits)."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -100))) - 7)


def judge_served(ref_logits, prompt_len: int, served) -> dict:
    """Served token j was chosen after position ``prompt_len - 1 + j``
    of the teacher-forced sequence.  It must be the reference's argmax
    there, or lie within ``TIE_ULPS`` bf16 ulps of the top logit (with
    seeded random weights the top two of 100k bf16 logits tie every ~20
    tokens)."""
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
            "tie_gaps_ulps": gaps, "not_ties": bad, "ok": not bad}


# Logits of the bf16 program against the float32 reference, as the
# relative L2 error over a slice.  bf16 keeps 8 significant bits (a
# relative rounding of 2^-9 ~ 0.2 % per operation); through 8 layers of
# some ten rounded operations each, with errors adding as a random walk,
# that comes to about 1-2 %.  An 8-bit float (3 significant bits fewer)
# or a dropped term would give ten times that, so 4 % separates them.
LOGITS_REL_L2_TOL = 0.04


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
