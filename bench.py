"""Headline benchmark: Llama-3-family pretraining tokens/sec/chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The reference's headline metric is Llama-3-8B pretraining tokens/sec/chip
with MFU >= 40% as the north star (BASELINE.md).  This bench runs a
compiled (jit, donated-state) bf16 training step of the Llama-3
architecture at the TRUE recipe shape — vocab 128,256, sequence 8192 —
at the largest (model, batch) from the ladder that fits the local chip's
HBM, measures steady-state tokens/sec over >=20 iterations, and reports
BOTH MFU conventions (6N, and 6N + causal-attention FLOPs) as
BASELINE.md promises.  ``vs_baseline`` is MFU(6N)/0.40 (no
reference-published numbers exist: BASELINE.json ``published`` is {}).

``python bench.py --ladder`` additionally measures the BASELINE.md
measurement-ladder rows that fit one chip (GPT-2 124M, Llama true-shape,
Qwen2-MoE, decode tokens/sec) and prints one JSON line per row.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Peak dense bf16 FLOP/s and HBM bytes per chip, by normalized
# PJRT device_kind substring (e.g. "TPU v5 lite" -> v5lite).
_CHIP_TABLE = [
    ("v6e", 918e12, 32e9), ("v6", 918e12, 32e9), ("v5p", 459e12, 95e9),
    ("v5e", 197e12, 16e9), ("v5lite", 197e12, 16e9), ("v4", 275e12, 32e9),
    ("v3", 123e12, 16e9), ("v2", 46e12, 8e9),
]


def _chip_info(kind: str):
    k = kind.lower().replace(" ", "").replace("tpu", "")
    for sub, peak, hbm in _CHIP_TABLE:
        if sub in k:
            return peak, hbm
    return None, None


# (name, hidden, intermediate, layers, heads, kv_heads)
_LADDER = [
    ("llama3-8b", 4096, 14336, 32, 32, 8),
    ("llama-3b", 3072, 8192, 26, 24, 8),
    ("llama-1b", 2048, 8192, 16, 16, 8),
    ("llama-770m", 1536, 6144, 16, 12, 4),
    ("llama-410m", 1024, 4096, 12, 8, 4),
    ("llama-tiny", 256, 512, 4, 8, 4),
]

_SEQ = 8192          # Llama-3-8B recipe sequence length (BASELINE.md)
_VOCAB = 128256      # Llama-3 true vocab — the lm-head/CE matmul at size


def _param_count(h, i, layers, heads, kv, vocab):
    head_dim = h // heads
    attn = h * heads * head_dim + 2 * h * kv * head_dim + heads * head_dim * h
    mlp = 3 * h * i
    per_layer = attn + mlp + 2 * h
    return layers * per_layer + 2 * vocab * h + h


def _fits(n_params, batch, seq, h, layers, hbm_bytes):
    # bf16 param + bf16 grad + 2x f32 adam moments = 12 B/param; remat'd
    # layer-boundary activations; fused CE keeps logits chunked.  Margins
    # calibrated on v5e (16 GB): llama-770m/b2/s8192/v128256 fits (13 GB
    # state+acts), b4 does not.
    acts = batch * seq * h * layers * 4
    need = (n_params * 12 + acts) * 1.15 + 0.9e9
    return need <= hbm_bytes


def _candidates():
    """Every (model, batch) in ladder order, largest first — the single
    enumeration shared by the analytic pick and the OOM backoff."""
    for name, h, i, layers, heads, kv in _LADDER:
        n = _param_count(h, i, layers, heads, kv, _VOCAB)
        for batch in (16, 8, 4, 2, 1):
            yield name, h, i, layers, heads, kv, batch, n


def _pick_config(hbm_bytes, seq):
    for cand in _candidates():
        name, h, i, layers, heads, kv, batch, n = cand
        if _fits(n, batch, seq, h, layers, hbm_bytes):
            return cand
    name, h, i, layers, heads, kv = _LADDER[-1]
    return name, h, i, layers, heads, kv, 1, _param_count(
        h, i, layers, heads, kv, _VOCAB)


def _device():
    import jax
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "cpu")
    peak, hbm_table = _chip_info(kind)
    stats = {}
    try:
        stats = dev.memory_stats() or {}
    except Exception:
        pass
    hbm = stats.get("bytes_limit") or hbm_table or 8e9
    on_tpu = dev.platform not in ("cpu",)
    return dev, kind, peak, hbm, on_tpu


def _time_step(step, data, iters):
    import jax
    loss = step(data)
    jax.device_get(loss)
    loss = step(data)
    jax.device_get(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(data)
    # device_get is the timing barrier: it waits for the whole
    # donated-state chain of every step dispatched above
    jax.device_get(loss)
    dt = time.perf_counter() - t0
    return dt / iters, loss


def _mfu_pair(n_params, layers, h, seq, tokens_per_sec, peak):
    """Both BASELINE.md MFU conventions: 6N, and 6N + causal-attention
    FLOPs (per token per layer: QK^T + PV = 4*s*h full, /2 causal, x3
    fwd+bwd => 6*s*h)."""
    if not peak:
        return None, None
    f6n = 6 * n_params
    fattn = f6n + 6 * layers * seq * h
    return (f6n * tokens_per_sec / peak, fattn * tokens_per_sec / peak)


def _train_batch(vocab, batch, seq):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((batch, 1), -100, np.int32)], axis=1)
    return {"input_ids": ids, "labels": labels}


def _is_oom(e: Exception) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Ran out of memory" in s
            or "out of memory" in s.lower())


def _backoff_candidates(hbm, seq):
    """The analytic pick first, then every strictly-smaller
    (model, batch) from the SAME enumeration — probe-and-backoff for
    chips where the v5e-calibrated _fits margins misjudge (VERDICT r2
    weak #6)."""
    import itertools
    first = _pick_config(hbm, seq)
    yield first
    rest = itertools.dropwhile(lambda c: c != first, _candidates())
    for cand in itertools.islice(rest, 1, None):
        yield cand


def bench_headline(emit=True):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    dev, kind, peak, hbm, on_tpu = _device()
    seq = _SEQ if on_tpu else 256
    last_err = None
    for cand in _backoff_candidates(hbm if on_tpu else 4e9, seq):
        name, h, i, layers, heads, kv, batch, n_params = cand
        cfg = LlamaConfig(
            vocab_size=_VOCAB if on_tpu else 1024, hidden_size=h,
            intermediate_size=i, num_hidden_layers=layers,
            num_attention_heads=heads, num_key_value_heads=kv,
            max_position_embeddings=seq, recompute=True,
            recompute_granularity="core_attn")
        if not on_tpu:
            n_params = _param_count(h, i, layers, heads, kv,
                                    cfg.vocab_size)
        try:
            model = LlamaForCausalLM(cfg)
            model = paddle.amp.decorate(model, level="O2",
                                        dtype="bfloat16")
            opt = paddle.optimizer.AdamW(
                learning_rate=1e-4, parameters=model.parameters(),
                grad_clip=paddle.ClipGradByGlobalNorm(1.0))
            step = CompiledTrainStep(
                model, lambda m, b: m(b["input_ids"],
                                      labels=b["labels"]), opt)
            data = _train_batch(cfg.vocab_size, batch, seq)
            step_time, loss = _time_step(step, data,
                                         20 if on_tpu else 2)
            break
        except Exception as e:
            if _is_oom(e) and on_tpu:
                last_err = e
                # release the failed attempt's device state (params +
                # moments) BEFORE probing the next candidate, or every
                # retry competes with the biggest failed allocation
                model = opt = step = None  # noqa: F841
                import gc
                gc.collect()
                print(json.dumps({"note": "oom_backoff",
                                  "config": f"{name}/b{batch}"}),
                      file=sys.stderr, flush=True)
                continue
            raise
    else:
        raise RuntimeError(
            f"no headline config fits this chip: {last_err}")

    tokens_per_sec = batch * seq / step_time
    mfu6n, mfu_attn = _mfu_pair(n_params, layers, h, seq, tokens_per_sec,
                                peak)
    vs_baseline = (mfu6n / 0.40) if mfu6n is not None else None

    result = {
        "metric": f"{name}_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs_baseline, 4) if vs_baseline else None,
        "extra": {"device_kind": kind, "params": n_params,
                  "batch": batch, "seq": seq,
                  "step_time_s": round(step_time, 4),
                  "mfu": round(mfu6n, 4) if mfu6n is not None else None,
                  "mfu_attn": round(mfu_attn, 4)
                  if mfu_attn is not None else None,
                  "vocab": cfg.vocab_size,
                  "final_loss": float(np.asarray(jax.device_get(loss)))},
    }
    if emit:
        print(json.dumps(result))
    return result


# ---------------------------------------------------------------------------
# BASELINE.md measurement ladder (--ladder)
# ---------------------------------------------------------------------------

def bench_gpt2():
    """Ladder #1: GPT-2 124M steps/sec (single device)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)

    _, kind, peak, _, on_tpu = _device()
    cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=1024)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, b: crit(m(b["x"]), b["y"]),
                             opt)
    batch, seq = (8, 1024) if on_tpu else (2, 128)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    data = {"x": ids[:, :-1], "y": ids[:, 1:].astype(np.int64)}
    step_time, loss = _time_step(step, data, 20 if on_tpu else 2)
    return {"metric": "gpt2-124m_steps_per_sec", "unit": "steps/sec",
            "value": round(1.0 / step_time, 3),
            "extra": {"device_kind": kind, "batch": batch, "seq": seq,
                      "tokens_per_sec": round(batch * seq / step_time, 1),
                      "final_loss": float(np.asarray(jax.device_get(loss)))}}


def bench_moe():
    """Ladder #5: Qwen2-MoE-architecture tokens/sec (single chip; EP
    all-to-all becomes GSPMD collectives on a mesh)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)

    _, kind, peak, hbm, on_tpu = _device()
    # moe-360m-class: 8 experts top-2 + shared, fits v5e comfortably
    cfg = Qwen2MoeConfig(
        vocab_size=_VOCAB if on_tpu else 512, hidden_size=1024,
        moe_intermediate_size=704,
        shared_expert_intermediate_size=2816,
        num_hidden_layers=12 if on_tpu else 2,
        num_attention_heads=8, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, recompute=on_tpu,
        max_position_embeddings=4096 if on_tpu else 128)
    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, b: m(b["input_ids"],
                                                   labels=b["labels"]), opt)
    batch, seq = (4, 4096) if on_tpu else (2, 128)
    data = _train_batch(cfg.vocab_size, batch, seq)
    step_time, loss = _time_step(step, data, 20 if on_tpu else 2)
    return {"metric": "qwen2-moe-class_tokens_per_sec_per_chip",
            "unit": "tokens/sec", "value": round(batch * seq / step_time, 1),
            "extra": {"device_kind": kind, "batch": batch, "seq": seq,
                      "experts": 8,
                      "final_loss": float(np.asarray(jax.device_get(loss)))}}


def bench_ernie():
    """Ladder #3: ERNIE-4.5-class (dense backbone of the TP+PP recipe;
    pp/mp degrees only exist on multi-chip meshes — the dryrun validates
    them, this measures single-chip throughput of the same model)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.ernie import Ernie45Config, Ernie45ForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = Ernie45Config(vocab_size=103424, hidden_size=1536,
                            intermediate_size=6144, num_hidden_layers=16,
                            num_attention_heads=12, num_key_value_heads=4,
                            max_position_embeddings=8192, recompute=True)
        batch, seq = 2, 8192
    else:
        from paddle_tpu.models.ernie import ernie45_tiny_config
        cfg = ernie45_tiny_config()
        batch, seq = 2, 64
    paddle.seed(0)
    model = Ernie45ForCausalLM(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, b: m(b["input_ids"],
                                                   labels=b["labels"]), opt)
    data = _train_batch(cfg.vocab_size, batch, seq)
    step_time, loss = _time_step(step, data, 20 if on_tpu else 2)
    h, layers = cfg.hidden_size, cfg.num_hidden_layers
    n = _param_count(h, cfg.intermediate_size, layers,
                     cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.vocab_size)
    tps = batch * seq / step_time
    mfu6n, mfu_attn = _mfu_pair(n, layers, h, seq, tps, peak)
    return {"metric": "ernie45-class_tokens_per_sec_per_chip",
            "unit": "tokens/sec", "value": round(tps, 1),
            "extra": {"device_kind": kind, "batch": batch, "seq": seq,
                      "params": n,
                      "mfu": round(mfu6n, 4) if mfu6n else None,
                      "mfu_attn": round(mfu_attn, 4) if mfu_attn else None,
                      "final_loss": float(np.asarray(jax.device_get(loss)))}}


def bench_dit():
    """Ladder #4: DiT (conv+groupnorm family) imgs/sec."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.dit import DiTConfig, DiTWithDiffusion

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        # DiT-L/2-class on 32x32x4 latents (batch sized for 16 GB with
        # full activations — DiT has no remat knob yet)
        cfg = DiTConfig(input_size=32, patch_size=2, hidden_size=1024,
                        depth=24, num_heads=16)
        batch = 16
    else:
        from paddle_tpu.models.dit import dit_tiny_config
        cfg = dit_tiny_config()
        batch = 4
    paddle.seed(0)
    model = DiTWithDiffusion(cfg)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, b: m(b["x"], b["y"]), opt)
    rng = np.random.default_rng(0)
    data = {"x": rng.standard_normal(
        (batch, cfg.in_channels, cfg.input_size, cfg.input_size)
    ).astype(np.float32),
        "y": rng.integers(0, cfg.num_classes, (batch,)).astype(np.int32)}
    step_time, loss = _time_step(step, data, 20 if on_tpu else 2)
    return {"metric": "dit-l2_imgs_per_sec", "unit": "imgs/sec",
            "value": round(batch / step_time, 1),
            "extra": {"device_kind": kind, "batch": batch,
                      "step_time_s": round(step_time, 4),
                      "final_loss": float(np.asarray(jax.device_get(loss)))}}


def bench_decode():
    """Decode tokens/sec through the jitted generate() loop."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, prompt, new = 8, 128, 256
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, prompt, new = 2, 8, 16
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, prompt), dtype=np.int32))
    out, _ = model.generate(ids, max_new_tokens=new)  # compile
    t0 = time.perf_counter()
    out, _ = model.generate(ids, max_new_tokens=new)
    out.numpy()
    dt = time.perf_counter() - t0
    return {"metric": "llama-770m_decode_tokens_per_sec",
            "unit": "tokens/sec", "value": round(batch * new / dt, 1),
            "extra": {"device_kind": kind, "batch": batch,
                      "prompt": prompt, "new_tokens": new,
                      "per_seq_tokens_per_sec": round(new / dt, 1)}}


def bench_moe_deepseek():
    """DeepSeekMoE-class kernel row (VERDICT r3 Weak #2): 64
    fine-grained experts top-6 at H=2048/F=1408 — the many-expert
    regime the grouped tiles were autotuned for in round 4.  Marginal
    per-iteration device time ((len40-len8)/32, cancels the fixed
    per-call dispatch cost) of the dropless grouped path vs the
    capacity-padded dense GShard einsums."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.grouped_matmul import dropless_moe_ffn

    _, kind, peak, hbm, on_tpu = _device()
    if not on_tpu:
        return {"metric": "deepseek_moe_grouped_vs_dense",
                "unit": "ratio", "value": -1.0,
                "extra": {"note": "tpu_only_row"}}
    E, H, F, K, T = 64, 2048, 1408, 6, 4096
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, H)) * 0.1, jnp.bfloat16)
    gv = jnp.asarray(np.abs(rng.standard_normal((T, K))), jnp.float32)
    ei = jnp.asarray(rng.integers(0, E, (T, K)), jnp.int32)
    wg = jnp.asarray(rng.standard_normal((E, H, F)) * .02, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((E, H, F)) * .02, jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((E, F, H)) * .02, jnp.bfloat16)

    def marginal(mk_body):
        def run_n(n):
            def f(x, wg, wu, wd):
                c, _ = jax.lax.scan(mk_body(wg, wu, wd), x, None,
                                    length=n)
                return c.astype(jnp.float32).sum()
            g = jax.jit(f)
            jax.device_get(g(x, wg, wu, wd))
            best = 1e9
            for _ in range(3):
                t0 = time.perf_counter()
                jax.device_get(g(x, wg, wu, wd))
                best = min(best, time.perf_counter() - t0)
            return best
        return (run_n(40) - run_n(8)) / 32

    def grouped_mk(wg, wu, wd):
        def body(c, _):
            y = dropless_moe_ffn(c, gv, ei, wg, wu, wd)  # autotuned tm
            return (c + y.astype(c.dtype)) * jnp.bfloat16(0.5), None
        return body

    def dense_mk(wg, wu, wd):
        C = int(np.ceil(T * K / E * 1.25))
        onehot = jax.nn.one_hot(ei, E, dtype=jnp.int32)
        flat = onehot.reshape(T * K, E)
        pos = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
        in_cap = (pos < C) & (onehot > 0)
        pc = jax.nn.one_hot(jnp.where(in_cap, pos, C), C + 1,
                            dtype=jnp.bfloat16)[..., :C]
        disp = jnp.einsum("tke,tkec->tec", onehot.astype(jnp.bfloat16)
                          * in_cap.astype(jnp.bfloat16), pc)

        def body(c, _):
            xe = jnp.einsum("tec,th->ech", disp, c)
            h1 = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, wg))
            h1 = h1 * jnp.einsum("ech,ehf->ecf", xe, wu)
            eo = jnp.einsum("ecf,efh->ech", h1, wd)
            y = jnp.einsum("ech,tec->th", eo, disp)
            return (c + y.astype(c.dtype)) * jnp.bfloat16(0.5), None
        return body

    t_g = marginal(grouped_mk)
    t_d = marginal(dense_mk)
    return {"metric": "deepseek_moe_grouped_vs_dense", "unit": "ratio",
            "value": round(t_d / t_g, 3),
            "extra": {"device_kind": kind,
                      "experts": E, "top_k": K, "tokens": T,
                      "grouped_ms_per_layer": round(t_g * 1e3, 2),
                      "dense_ms_per_layer": round(t_d * 1e3, 2),
                      "note": "marginal (len40-len8)/32 in-graph; "
                              "r5: fused gate|up GLU kernel + "
                              "tm=256/full-K retune -> ~0.96x dense "
                              "(padding-bound at 64E, see BASELINE.md "
                              "5b); r3's auto tile was 1.39x SLOWER"}}


def bench_paged_kernel():
    """On-chip serving KERNEL row (VERDICT r3 Missing #6): per-decode-
    step device time of the fused paged append+attend kernel vs the
    dense-cache decode attention, both lax.scan-serialized IN-GRAPH so
    host dispatch latency cannot contaminate the numbers (the engine
    row below is host-bound).  llama-770m attention
    geometry at batch 8 x 2048 context."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import _nn
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_append_attend)

    _, kind, peak, hbm, on_tpu = _device()
    if not on_tpu:
        return {"metric": "paged_decode_kernel_us_per_step",
                "unit": "us", "value": -1.0,
                "extra": {"note": "tpu_only_row"}}
    B, H, KVH, D, PAGE, CTX = 8, 12, 4, 128, 128, 2048
    MAXP, N = CTX // PAGE, 256
    rng = np.random.default_rng(0)
    kp0 = jnp.asarray(rng.standard_normal((KVH, B * MAXP, PAGE, D)) * .1,
                      jnp.bfloat16)
    vp0 = jnp.asarray(rng.standard_normal((KVH, B * MAXP, PAGE, D)) * .1,
                      jnp.bfloat16)
    table = jnp.asarray(rng.permutation(B * MAXP).reshape(B, MAXP),
                        jnp.int32)
    lens0 = jnp.full((B,), CTX - N - 1, jnp.int32)
    k_new = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.bfloat16)
    q3 = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    kd0 = jnp.asarray(rng.standard_normal((B, CTX, KVH, D)) * .1,
                      jnp.bfloat16)
    q4 = q3[:, None]

    def timed(f, *args):
        f = jax.jit(f)
        jax.block_until_ready(f(*args))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best / N * 1e6

    def paged(kp, vp, lens):
        def body(c, _):
            kp, vp, lens, q_ = c
            o, kp, vp = paged_decode_append_attend(
                q_, kp, vp, k_new, k_new, table, lens)
            return (kp, vp, lens + 1, q3 + o * 1e-6), None
        c, _ = jax.lax.scan(body, (kp, vp, lens, q3), None, length=N)
        return c[3]

    def dense(kd, vd, lens):
        def body(c, _):
            kd, vd, lens, q_ = c
            kd = jax.lax.dynamic_update_slice(
                kd, (k_new + kd[0, 0, 0, 0] * 0)[:, None],
                (0, lens[0], 0, 0))
            vd = jax.lax.dynamic_update_slice(vd, k_new[:, None],
                                              (0, lens[0], 0, 0))
            lens = lens + 1
            am = jnp.where(jnp.arange(CTX)[None, :] < lens[:, None],
                           0.0, -1e30)[:, None, None, :]
            o = _nn.scaled_dot_product_attention(q_, kd, vd,
                                                 attn_mask=am)
            return (kd, vd, lens, q4 + o * 1e-6), None
        c, _ = jax.lax.scan(body, (kd, vd, lens, q4), None, length=N)
        return c[3]

    t_paged = timed(paged, kp0, vp0, lens0)
    t_dense = timed(dense, kd0, vp0.reshape(B, CTX, KVH, D), lens0)

    # ragged-vs-split dispatch row (ISSUE 16): the SAME mixed batch —
    # 6 decode rows + 2 prefill chunks of 64 — as ONE ragged dispatch
    # vs the split path it replaced (decode kernel + one dispatch per
    # chunk).  Wall-clock per round on purpose: the delta IS the
    # per-dispatch host overhead the ragged program amortizes away.
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_append_attend)
    CH, S = 64, 8
    T = 6 + 2 * CH
    qr = jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
    knr = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)
    vnr = jnp.asarray(rng.standard_normal((T, KVH, D)), jnp.bfloat16)
    dec_kv, pre_kv = CTX - N - 1, 512        # 512 % PAGE == 0
    qs = jnp.asarray(list(range(6)) + [6, 6 + CH], jnp.int32)
    ql_mix = jnp.asarray([1] * 6 + [CH, CH], jnp.int32)
    kv_mix = jnp.asarray([dec_kv] * 6 + [pre_kv, pre_kv], jnp.int32)
    ql_chunk = [jnp.asarray([0] * 6 + ([CH, 0] if s == 0 else [0, CH]),
                            jnp.int32) for s in range(2)]
    qd, knd, vnd = qr[:6], knr[:6], vnr[:6]
    lens6 = jnp.full((6,), dec_kv, jnp.int32)

    def ragged_round(kp, vp):
        _, kp, vp = ragged_paged_append_attend(
            qr, kp, vp, knr, vnr, qs, ql_mix, kv_mix, table)
        return kp, vp

    def split_round(kp, vp):
        _, kp, vp = paged_decode_append_attend(
            qd, kp, vp, knd, vnd, table[:6], lens6)
        for ql in ql_chunk:                  # one dispatch per chunk
            _, kp, vp = ragged_paged_append_attend(
                qr, kp, vp, knr, vnr, qs, ql, kv_mix, table)
        return kp, vp

    def timed_round(fn, rounds=32):
        kp, vp = kp0 + 0, vp0 + 0            # donation consumes pools
        kp, vp = fn(kp, vp)                  # compile + warm
        jax.block_until_ready((kp, vp))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(rounds):
                kp, vp = fn(kp, vp)
            jax.block_until_ready((kp, vp))
            best = min(best, time.perf_counter() - t0)
        return best / rounds * 1e6

    t_ragged = timed_round(ragged_round)
    t_split = timed_round(split_round)
    return {"metric": "paged_decode_kernel_us_per_step",
            "unit": "us", "value": round(t_paged, 1),
            "extra": {"device_kind": kind, "batch": B, "context": CTX,
                      "page_size": PAGE,
                      "dense_us_per_step": round(t_dense, 1),
                      "paged_over_dense": round(t_paged / t_dense, 2),
                      "ragged_mixed_us_per_round": round(t_ragged, 1),
                      "split_mixed_us_per_round": round(t_split, 1),
                      "ragged_over_split": round(t_ragged / t_split, 2),
                      "ragged_note": "6 decode rows + 2x64-token "
                                     "prefill chunks: ONE ragged "
                                     "dispatch vs decode kernel + "
                                     "per-chunk dispatches (wall-"
                                     "clock: the delta is host "
                                     "dispatch overhead)",
                      "note": "fused append+attend kernel, in-graph "
                              "scan x256; r3 path was ~18x dense; the "
                              "dense comparator sped up ~25% when sdpa "
                              "moved to the shard_map flash dispatch "
                              "(r5), so expect ~1.25-1.35x — the "
                              "kernel itself is unchanged "
                              "(bisect-verified, BASELINE.md)"}}


def bench_engine_window():
    """Device-level serving-SYSTEM row (VERDICT r4 Missing #6): the
    ENGINE's multi-step decode window — sampling + page bookkeeping +
    the fused append+attend kernel, all inside one XLA program
    (_paged_decode_step) — timed as the MARGINAL cost per token
    between a 64-token and a 16-token window (cancels the fixed
    per-call dispatch cost), at the 770m geometry, batch 8 x 2048 ctx.
    Unlike the kernel row (attention only), this is the whole decode
    path the engine actually dispatches per window."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine, _paged_decode_step
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if not on_tpu:
        return {"metric": "llama-770m_engine_window_us_per_token",
                "unit": "us/token", "value": -1.0,
                "extra": {"note": "tpu_only_row"}}
    cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                      intermediate_size=6144, num_hidden_layers=16,
                      num_attention_heads=12, num_key_value_heads=4,
                      max_position_embeddings=2048)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    batch, ctx, page = 8, 2048, 128
    eng = LLMEngine(model, max_seqs=batch, max_len=ctx, page_size=page,
                    dtype=jnp_bf16(), steps_per_sync=16)
    rng = np.random.default_rng(0)
    # the 1024-token prompts prefill each sequence to a realistic
    # cache depth; allocate() reserved page capacity for the decode
    for i in range(batch):
        eng.add_request(f"w{i}",
                        rng.integers(1, cfg.vocab_size, 1024).tolist(),
                        max_new_tokens=512)
    slots = np.array([r.slot for r in eng._active])
    lens = jnp.asarray(eng.cache.seq_lens[slots], np.int32)
    tables = jnp.asarray(eng.cache.page_table[slots])
    tokens = jnp.asarray([r.out[-1] for r in eng._active], np.int32)
    key = jax.random.PRNGKey(0)

    def run(n_steps):
        toks, kp, vp, ks, vs = _paged_decode_step(
            eng._stack, eng._norm_w, eng._head_w, eng._embed_w,
            eng._rope, eng.cache.k_pages, eng.cache.v_pages,
            eng.cache.k_scales, eng.cache.v_scales, tokens,
            lens, tables, lens, key, eps=eng.eps, kvh=eng.kvh,
            head_dim=eng.head_dim, transpose_head=eng._tied,
            strategy="greedy_search", n_steps=n_steps)
        eng.cache.k_pages, eng.cache.v_pages = kp, vp
        eng.cache.k_scales, eng.cache.v_scales = ks, vs
        return float(np.asarray(jax.device_get(toks))[0, 0])

    for n in (16, 64):                        # compile + warm both
        run(n)
    t16 = t64 = 1e9
    for _ in range(3):
        t0 = time.perf_counter(); run(16)
        t16 = min(t16, time.perf_counter() - t0)
        t0 = time.perf_counter(); run(64)
        t64 = min(t64, time.perf_counter() - t0)
    per_tok = (t64 - t16) / 48
    return {"metric": "llama-770m_engine_window_us_per_token",
            "unit": "us/token", "value": round(per_tok * 1e6, 1),
            "extra": {"device_kind": kind, "batch": batch,
                      "ctx_tokens": 1024, "page_size": page,
                      "tokens_per_sec_device":
                          round(batch / per_tok, 1),
                      "note": "marginal (64-16)-step windows; full "
                              "engine path in-graph (sampling + page "
                              "bookkeeping + fused append+attend)"}}


def bench_decode_window():
    """Scanned decode-window row (ISSUE 16): decode tokens/sec through
    the engine with the ``steps_per_sync`` window host-chained
    (``scan_decode=False``: nsteps dispatches per window) vs ON-DEVICE
    (one compiled while_loop program per window), at steps_per_sync
    1/4/16 on a decode-heavy small batch — the regime where
    per-dispatch overhead dominates.  CPU-runnable on the tiny config;
    rounds are INTERLEAVED best-of-3 so load drift cannot favor either
    path."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_tiny_config)

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        plens, new, page, mlen = [96, 57, 128, 101], 256, 128, 2048
        dtype = jnp_bf16()
    else:
        cfg = llama_tiny_config()
        plens, new, page, mlen = [8, 5], 33, 8, 64
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in plens]

    def run(sps, scan):
        eng = LLMEngine(model, max_seqs=len(prompts), max_len=mlen,
                        page_size=page, dtype=dtype,
                        steps_per_sync=sps, scan_decode=scan)
        for i, p in enumerate(prompts):
            eng.add_request(f"w{i}", p, max_new_tokens=new)
        eng.step()                           # prefill outside the clock
        base = sum(len(r.out) for r in eng.requests.values())
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out) for r in eng.requests.values()) - base
        return toks / dt

    cfgs = [(1, False), (4, False), (4, True), (16, False), (16, True)]
    for sps, scan in cfgs:                   # compile warm-up passes
        run(sps, scan)
    best = {c: 0.0 for c in cfgs}
    for _ in range(3):                       # interleaved best-of
        for c in cfgs:
            best[c] = max(best[c], run(*c))
    rows = {f"sps{sps}_{'scan' if sc else 'host'}_tokens_per_sec":
            round(v, 1) for (sps, sc), v in best.items()}
    return {"metric": "engine_decode_window_tokens_per_sec",
            "unit": "tokens/sec", "value": round(best[(16, True)], 1),
            "extra": {"device_kind": kind, "batch": len(prompts),
                      "new_tokens": new, **rows,
                      "scan_over_host_sps4":
                          round(best[(4, True)] / best[(4, False)], 2),
                      "scan_over_host_sps16":
                          round(best[(16, True)] / best[(16, False)],
                                2),
                      "window_compiles": LLMEngine.window_compiles(),
                      "note": "decode-heavy small batch; scanned "
                              "window = ONE while_loop program per "
                              "steps_per_sync window (early-exit on "
                              "all-rows-done) vs host-chained "
                              "per-token dispatch"}}


def bench_engine():
    """Serving-engine row: continuous-batching decode tokens/sec through
    the paged-KV LLMEngine (chunked ragged prefill admission + paged
    attention decode) — host-dispatch-bound; the device-level number
    is bench_engine_window below."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page = 8, 256, 128
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]  # ragged lengths
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page = 2, 16, 8
        prompts = [8, 5]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    dtype = np.float32 if not on_tpu else jnp_bf16()
    sync = 16 if on_tpu else 4   # multi-step decode amortizes dispatch
    eng = LLMEngine(model, max_seqs=batch, max_len=2048 if on_tpu else 32,
                    page_size=page, dtype=dtype, steps_per_sync=sync)
    for i, plen in enumerate(prompts):
        eng.add_request(
            f"w{i}", rng.integers(1, cfg.vocab_size, plen).tolist(),
            max_new_tokens=new)
    # warmup: one decode window compiles the step fn
    eng.step()
    produced0 = sum(len(r.out) for r in eng.requests.values())
    calls = 0
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
        calls += 1
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in eng.requests.values()) - produced0
    return {"metric": "llama-770m_engine_decode_tokens_per_sec",
            "unit": "tokens/sec", "value": round(total / dt, 1),
            "extra": {"device_kind": kind, "max_seqs": batch,
                      "prompt_lens": prompts, "new_tokens": new,
                      "steps_per_sync": sync, "dispatches": calls,
                      "prefill_compiles": LLMEngine.prefill_compiles(),
                      "decode_compiles": LLMEngine.decode_compiles()}}


def bench_serving_quant():
    """Quantized-serving row (ISSUE 1): decode tokens/sec through the
    engine with an fp KV cache vs the INT8 paged KV cache (per-token
    scales, in-kernel dequant on TPU), plus the EFFECTIVE PAGE
    CAPACITY the int8 cache buys at an equal HBM budget vs fp16 —
    the bandwidth/capacity win is the point of the subsystem, so the
    row reports both.  Same JSON shape as the headline metric so
    BENCH_*.json rounds can track the quantized path."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.inference.paged_cache import PagedKVCache
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        fp_dtype = jnp_bf16()
        fp_kv = "bfloat16"
    else:
        # tiny model, but the SERVING head_dim (128): the capacity
        # claim is per-token bytes D+4 vs 2D, a function of head_dim
        cfg = LlamaConfig(vocab_size=256, hidden_size=256,
                          intermediate_size=512, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          max_position_embeddings=128,
                          rope_theta=10000.0)
        batch, new, page, maxlen, sync = 2, 16, 8, 64, 4
        prompts = [8, 5]
        fp_dtype = np.float32
        fp_kv = None
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)

    def run(kv_dtype):
        eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                        page_size=page, dtype=fp_dtype,
                        steps_per_sync=sync, kv_dtype=kv_dtype)
        for i, plen in enumerate(prompts):
            eng.add_request(
                f"w{i}", rng.integers(1, cfg.vocab_size, plen).tolist(),
                max_new_tokens=new)
        eng.step()                   # warmup: compile the decode window
        produced0 = sum(len(r.out) for r in eng.requests.values())
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        total = sum(len(r.out) for r in eng.requests.values()) - produced0
        return total / dt, eng

    tps_fp, _ = run(fp_kv)
    tps_q, eng_q = run("int8")

    # effective page capacity at an EQUAL HBM budget, vs an fp16 cache
    # (honest accounting: int8 pages carry their f32 scale rows)
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    geom = dict(n_pages=2, page_size=page,
                n_kv_heads=cfg.num_key_value_heads, head_dim=head_dim,
                max_seqs=1, max_len=page,
                num_layers=cfg.num_hidden_layers)
    bpt_fp16 = PagedKVCache(dtype=jnp.bfloat16, **geom) \
        .kv_bytes_per_token()
    bpt_int8 = eng_q.cache.kv_bytes_per_token()
    cap_ratio = bpt_fp16 / bpt_int8
    budget = hbm or 16e9
    page_bytes_fp16 = bpt_fp16 * page
    page_bytes_int8 = bpt_int8 * page
    return {
        "metric": "serving_decode_int8_vs_fp_kv_tokens_per_sec",
        "value": round(tps_q, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps_q / tps_fp, 3),
        "extra": {"device_kind": kind, "max_seqs": batch,
                  "new_tokens": new, "page_size": page,
                  "fp_kv_dtype": fp_kv or "float32",
                  "fp_tokens_per_sec": round(tps_fp, 1),
                  "int8_tokens_per_sec": round(tps_q, 1),
                  "kv_bytes_per_token_fp16": bpt_fp16,
                  "kv_bytes_per_token_int8": bpt_int8,
                  "int8_capacity_ratio_vs_fp16": round(cap_ratio, 3),
                  "pages_at_budget_fp16": int(budget // page_bytes_fp16),
                  "pages_at_budget_int8": int(budget // page_bytes_int8),
                  "hbm_budget_bytes": int(budget),
                  "prefill_compiles": LLMEngine.prefill_compiles(),
                  "decode_compiles": LLMEngine.decode_compiles()}}


def bench_serving_metrics():
    """Observability-overhead row (ISSUE 2): decode tokens/sec through
    the SAME engine workload with the metrics runtime off vs on.  The
    instrumentation records O(1) host floats per decode WINDOW (TPOT is
    a weighted histogram observe, not per-token), so the acceptance bar
    is <=2% throughput overhead with metrics enabled."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 96, 8, 128, 4
        prompts = [8, 5, 12, 9]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if not on_tpu:
        dtype = np.float32

    def run(enable):
        rng = np.random.default_rng(0)
        eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        steps_per_sync=sync, enable_metrics=enable)
        for i, plen in enumerate(prompts):
            eng.add_request(
                f"w{i}", rng.integers(1, cfg.vocab_size, plen).tolist(),
                max_new_tokens=new)
        eng.step()                     # warmup: compiles the window
        produced0 = sum(len(r.out) for r in eng.requests.values())
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        total = sum(len(r.out)
                    for r in eng.requests.values()) - produced0
        return total / dt, eng

    run(False)                         # shared compile + cache warmup
    # interleave the arms so host clock drift hits both equally; the
    # per-arm max is the usual best-of-N noise floor estimator (the
    # 1-core CI box jitters ~2-3% run to run, well above the true
    # instrumentation cost)
    off, on = [], []
    eng_on = None
    for _ in range(5):
        off.append(run(False)[0])
        rate, eng_on = run(True)
        on.append(rate)
    best_off, best_on = max(off), max(on)
    overhead = (best_off - best_on) / best_off
    snap = eng_on.metrics_snapshot()
    return {"metric": "llama_engine_metrics_overhead_pct",
            "unit": "percent", "value": round(overhead * 100, 2),
            "extra": {"device_kind": kind,
                      "tokens_per_sec_metrics_off": round(best_off, 1),
                      "tokens_per_sec_metrics_on": round(best_on, 1),
                      "ttft_p_mean_ms": round(
                          snap["ttft_seconds"]["mean"] * 1e3, 2),
                      "tpot_mean_us": round(
                          snap["tpot_seconds"]["mean"] * 1e6, 1),
                      "prefill_compiles": snap["prefill_compiles"],
                      "decode_compiles": snap["decode_compiles"],
                      "budget": "overhead <= 2%"}}


def bench_trace():
    """Tracing-overhead row (ISSUE 9): decode tokens/sec through the
    SAME scheduler-driven workload with the span tracer off vs on.
    Tracing-off is a strict no-op (one module-global read returning
    the NULL_SPAN singleton — the budget-guard test pins it), so the
    interesting number is tracing ON: spans are recorded per request /
    page chunk / decode WINDOW, never per token, and the acceptance
    bar is <=3% throughput overhead.  Also reports the TTFT tail
    (p50/p95) from the new histogram quantiles, and sanity-checks the
    compile-count invariants with tracing enabled."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import tracing as obs_tracing
    from paddle_tpu.serving import Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 96, 8, 128, 4
        prompts = [8, 5, 12, 9]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if not on_tpu:
        dtype = np.float32

    def run(enable):
        if enable:
            obs_tracing.enable_tracing(max_spans=16384)
        else:
            obs_tracing.disable_tracing()
        try:
            rng = np.random.default_rng(0)
            eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                            page_size=page, dtype=dtype,
                            steps_per_sync=sync)
            sched = Scheduler(eng)
            for i, plen in enumerate(prompts):
                sched.submit(
                    f"t{i}",
                    rng.integers(1, cfg.vocab_size, plen).tolist(),
                    max_new_tokens=new)
            sched.step()               # warmup: compiles the window
            produced0 = sum(len(r.out)
                            for r in eng.requests.values())
            t0 = time.perf_counter()
            sched.run_until_idle()
            dt = time.perf_counter() - t0
            total = sum(
                len(sched.result(f"t{i}"))
                for i in range(len(prompts))) - produced0
            return total / dt, eng
        finally:
            obs_tracing.disable_tracing()

    run(False)                         # shared compile + cache warmup
    off, on = [], []
    eng_on = None
    for _ in range(5):                 # interleaved best-of (clock
        off.append(run(False)[0])      # drift hits both arms equally)
        rate, eng_on = run(True)
        on.append(rate)
    best_off, best_on = max(off), max(on)
    overhead = (best_off - best_on) / best_off
    snap = eng_on.metrics_snapshot()
    return {"metric": "llama_serving_tracing_overhead_pct",
            "unit": "percent", "value": round(overhead * 100, 2),
            "extra": {"device_kind": kind,
                      "tokens_per_sec_tracing_off": round(best_off, 1),
                      "tokens_per_sec_tracing_on": round(best_on, 1),
                      "ttft_p50_ms": round(
                          snap["ttft_seconds"]["p50"] * 1e3, 2),
                      "ttft_p95_ms": round(
                          snap["ttft_seconds"]["p95"] * 1e3, 2),
                      "tpot_p95_us": round(
                          snap["tpot_seconds"]["p95"] * 1e6, 1),
                      "prefill_compiles": snap["prefill_compiles"],
                      "decode_compiles": snap["decode_compiles"],
                      "budget": "overhead <= 3%"}}


def bench_fleet_health():
    """Fleet-health-plane overhead row (ISSUE 14): decode tokens/sec
    through the SAME scheduler-driven workload with the health plane
    off vs on.  Health-off is a strict no-op (one module-global read
    returning NULL_HEALTH — the budget-guard test pins it); health ON
    adds two SlidingWindow observes per TTFT / decode WINDOW (never
    per token), so the acceptance bar is <=3% throughput overhead,
    with tokens bit-identical and the compile counts unchanged.  Also
    runs a chaos-interrupted ``fit`` (stop mid-epoch, then
    auto_resume) and reports the GoodputMeter's fractions — they sum
    to 1.0 by construction and restart_replay is nonzero only in the
    resumed run."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import health as obs_health
    from paddle_tpu.serving import FleetWatcher, ReplicaRouter, Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 96, 8, 128, 4
        prompts = [8, 5, 12, 9]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if not on_tpu:
        dtype = np.float32

    def run(enable):
        # both arms ride the SAME path (scheduler behind a one-replica
        # router); the ON arm additionally enables the health plane AND
        # runs a live FleetWatcher thread scraping fleet_snapshot()
        # concurrently — the realistic always-on cost
        if enable:
            obs_health.enable_health()
        else:
            obs_health.disable_health()
        watcher = None
        try:
            rng = np.random.default_rng(0)
            eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                            page_size=page, dtype=dtype,
                            steps_per_sync=sync)
            sched = Scheduler(eng)
            router = ReplicaRouter([sched], sleep=lambda s: None)
            if enable:
                watcher = FleetWatcher(router, interval=0.02)
                watcher.start()
            for i, plen in enumerate(prompts):
                router.submit(
                    f"h{i}",
                    rng.integers(1, cfg.vocab_size, plen).tolist(),
                    max_new_tokens=new)
            sched.step()               # warmup: compiles the window
            produced0 = sum(len(r.out)
                            for r in eng.requests.values())
            t0 = time.perf_counter()
            sched.run_until_idle()
            dt = time.perf_counter() - t0
            total = sum(
                len(sched.result(f"h{i}"))
                for i in range(len(prompts))) - produced0
            return total / dt, eng
        finally:
            if watcher is not None:
                watcher.stop()
            obs_health.disable_health()

    run(False)                         # shared compile + cache warmup
    off, on = [], []
    eng_on = None
    for _ in range(5):                 # interleaved best-of (clock
        off.append(run(False)[0])      # drift hits both arms equally)
        rate, eng_on = run(True)
        on.append(rate)
    best_off, best_on = max(off), max(on)
    overhead = (best_off - best_on) / best_off
    compiles = eng_on.prefill_compiles()

    # -- goodput/badput accounting under an injected mid-run kill ------
    import shutil
    import tempfile

    from paddle_tpu import nn, optimizer
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.io.dataloader import CheckpointableLoader, Dataset

    class _Arr(Dataset):
        def __init__(self, n=32):
            r = np.random.default_rng(23)
            self.x = r.normal(size=(n, 6)).astype(np.float32)
            self.y = r.normal(size=(n, 3)).astype(np.float32)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    class _StopAfter(Callback):
        def __init__(self, n):
            super().__init__()
            self.n, self.seen = n, 0

        def on_train_batch_end(self, step, logs=None):
            self.seen += 1
            if self.seen >= self.n:
                self.model.stop_training = True

    def _fit(seed, ckdir, **kw):
        paddle.seed(seed)
        m = paddle.Model(nn.Sequential(
            nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 3)))
        m.prepare(optimizer.AdamW(learning_rate=5e-3), nn.MSELoss())
        loader = CheckpointableLoader(_Arr(), batch_size=4,
                                      shuffle=True, seed=7)
        m.fit(loader, epochs=2, verbose=0, checkpoint_dir=ckdir,
              save_steps=3, **kw)
        return obs_health.get_health().goodput.report()

    ckdir = tempfile.mkdtemp(prefix="bench-fleet-health-")
    try:
        obs_health.enable_health()
        _fit(1, ckdir, callbacks=[_StopAfter(5)])   # injected kill
        rep = _fit(9, ckdir, auto_resume=True)      # "fresh process"
    finally:
        obs_health.disable_health()
        shutil.rmtree(ckdir, ignore_errors=True)
    frac = rep["fractions"]

    return {"metric": "llama_serving_health_overhead_pct",
            "unit": "percent", "value": round(overhead * 100, 2),
            "extra": {"device_kind": kind,
                      "tokens_per_sec_health_off": round(best_off, 1),
                      "tokens_per_sec_health_on": round(best_on, 1),
                      "prefill_compiles": compiles,
                      "goodput_fraction": round(rep["goodput"], 4),
                      "fractions": {k: round(v, 4)
                                    for k, v in sorted(frac.items())},
                      "fractions_sum": round(sum(frac.values()), 6),
                      "restart_replay_seconds": round(
                          rep["seconds"]["restart_replay"], 4),
                      "budget": "overhead <= 3%"}}


def bench_introspection():
    """Compile/memory introspection-plane overhead row (ISSUE 15):
    decode tokens/sec through the SAME router-fronted scheduler
    workload with the CompileWatch off vs on.  Off is a strict no-op
    (watched_call reads one module global and tail-calls the jit
    function — the budget-guard test pins the NULL identity); ON adds
    a jit-cache-size read around each dispatch WINDOW plus, on the
    window that actually compiles, one AOT lowering for cost analysis
    — so the acceptance bar is <=3% throughput overhead with tokens
    bit-identical and the one-compile counters unchanged.  The ON arm
    also scrapes /compilez-shaped and /memz-shaped snapshots each
    iteration (the realistic always-on cost of a dashboard poll)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import introspection as obs_insp
    from paddle_tpu.serving import ReplicaRouter, Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 96, 8, 128, 4
        prompts = [8, 5, 12, 9]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if not on_tpu:
        dtype = np.float32

    def run(enable):
        if enable:
            obs_insp.enable_compile_watch()
        else:
            obs_insp.disable_compile_watch()
        try:
            rng = np.random.default_rng(0)
            eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                            page_size=page, dtype=dtype,
                            steps_per_sync=sync)
            sched = Scheduler(eng)
            router = ReplicaRouter([sched], sleep=lambda s: None)
            for i, plen in enumerate(prompts):
                router.submit(
                    f"c{i}",
                    rng.integers(1, cfg.vocab_size, plen).tolist(),
                    max_new_tokens=new)
            sched.step()               # warmup: compiles the window
            produced0 = sum(len(r.out)
                            for r in eng.requests.values())
            t0 = time.perf_counter()
            sched.run_until_idle()
            dt = time.perf_counter() - t0
            snap = None
            if enable:
                # the dashboard-poll cost rides inside the ON arm
                snap = obs_insp.compilez_snapshot()
                obs_insp.memz_snapshot()
            total = sum(
                len(sched.result(f"c{i}"))
                for i in range(len(prompts))) - produced0
            return total / dt, eng, snap
        finally:
            obs_insp.disable_compile_watch()

    run(True)                          # shared compile + cache warmup
    off, on = [], []
    eng_on, snap_on = None, None
    for _ in range(5):                 # interleaved best-of (clock
        off.append(run(False)[0])      # drift hits both arms equally)
        rate, eng_on, snap_on = run(True)
        on.append(rate)
    n_recompiles = len(snap_on["recompiles"])
    best_off, best_on = max(off), max(on)
    overhead = (best_off - best_on) / best_off
    return {"metric": "llama_serving_introspection_overhead_pct",
            "unit": "percent", "value": round(overhead * 100, 2),
            "extra": {"device_kind": kind,
                      "tokens_per_sec_watch_off": round(best_off, 1),
                      "tokens_per_sec_watch_on": round(best_on, 1),
                      "prefill_compiles": eng_on.prefill_compiles(),
                      "mixed_compiles": eng_on.mixed_compiles(),
                      "recompile_events": n_recompiles,
                      "budget": "overhead <= 3%"}}


def bench_capsule():
    """Request-capsule plane overhead row (ISSUE 17): decode
    tokens/sec through the SAME router-fronted scheduler workload
    with capture off vs armed.  Off is a strict no-op (every capture
    site reads one module global and bails on ``enabled``); ARMED
    records the per-request capsule — prompt, config fingerprint, the
    window key chain, lifecycle — plus a /capsulez-shaped snapshot
    scrape each iteration (the always-on dashboard-poll cost).
    Acceptance bar is <=3% throughput overhead; the ON arm also
    replays one captured request afterwards (outside the timed
    region) and reports that the replay was bit-exact."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import capsule as obs_cap
    from paddle_tpu.serving import ReplicaRouter, Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 256, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 96, 8, 128, 4
        prompts = [8, 5, 12, 9]
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    if not on_tpu:
        dtype = np.float32

    def run(enable):
        # (the armed store stays live past the ON run — the post-run
        # replay below reads it; the OFF run resets it at entry)
        if enable:
            obs_cap.enable_capsule_capture()
        else:
            obs_cap.disable_capsule_capture()
        rng = np.random.default_rng(0)
        eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        steps_per_sync=sync)
        sched = Scheduler(eng)
        router = ReplicaRouter([sched], sleep=lambda s: None)
        for i, plen in enumerate(prompts):
            router.submit(
                f"c{i}",
                rng.integers(1, cfg.vocab_size, plen).tolist(),
                max_new_tokens=new)
        sched.step()                   # warmup: compiles the window
        produced0 = sum(len(r.out) for r in eng.requests.values())
        t0 = time.perf_counter()
        sched.run_until_idle()
        dt = time.perf_counter() - t0
        snap = None
        if enable:
            # the dashboard-poll cost rides inside the ON arm
            snap = obs_cap.get_capsule_store().capsulez()
        total = sum(
            len(sched.result(f"c{i}"))
            for i in range(len(prompts))) - produced0
        return total / dt, eng, snap

    run(True)                          # shared compile + cache warmup
    try:
        off, on = [], []
        eng_on, snap_on = None, None
        for _ in range(5):             # interleaved best-of (clock
            off.append(run(False)[0])  # drift hits both arms equally)
            rate, eng_on, snap_on = run(True)
            on.append(rate)
        # replay one capsule through the last ON engine — the proof
        # the recorded stream is bit-reproducible, untimed
        cap = obs_cap.get_capsule_store().get("c0")
        rep = obs_cap.replay_capsule(cap, eng_on)
        bit_exact = rep["first_divergence"] is None
    finally:
        obs_cap.disable_capsule_capture()
    best_off, best_on = max(off), max(on)
    overhead = (best_off - best_on) / best_off
    return {"metric": "llama_serving_capsule_overhead_pct",
            "unit": "percent", "value": round(overhead * 100, 2),
            "extra": {"device_kind": kind,
                      "tokens_per_sec_capture_off": round(best_off, 1),
                      "tokens_per_sec_capture_on": round(best_on, 1),
                      "captured_total": snap_on["captured_total"],
                      "replay_bit_exact": bit_exact,
                      "replay_steps_compared": rep["steps_compared"],
                      "budget": "overhead <= 3%"}}


def bench_serving_prefix():
    """Automatic-prefix-caching row (ISSUE 3): N requests sharing a
    long system prompt, admitted through the SAME engine workload with
    prefix caching off vs on (same process, so ``vs_baseline`` is an
    honest in-process ratio).  Reports the shared-prefix TTFT (the
    cached requests skip the shared chunks' prefill entirely) and the
    page capacity the sharing buys: pages in use after admission with
    sharing on vs off at the same request mix."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 32, 128, 2048, 8
        sys_len, sfx_len = 512, 17          # 4 shared pages per prompt
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 8, 8, 8, 128, 2
        sys_len, sfx_len = 16, 3            # 2 shared pages per prompt
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab_size, sys_len).tolist()
    suffixes = [rng.integers(1, cfg.vocab_size, sfx_len).tolist()
                for _ in range(batch)]

    def run(enable):
        eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        steps_per_sync=sync,
                        enable_prefix_caching=enable)
        ttfts = []
        for i, sfx in enumerate(suffixes):
            t0 = time.perf_counter()
            eng.add_request(f"p{i}", sys_prompt + sfx,
                            max_new_tokens=new)
            ttfts.append(time.perf_counter() - t0)
        pages_used = (eng.cache.n_pages - 1) - eng.cache.free_page_count()
        while eng.has_work():
            eng.step()
        # request 0 is the compulsory miss that populates the cache;
        # the shared-prefix TTFT is the mean over the rest
        return float(np.mean(ttfts[1:])), pages_used, eng

    run(False)                        # warmup: compiles prefill+decode
    ttft_off, pages_off, _ = run(False)
    ttft_on, pages_on, eng = run(True)
    st = eng.prefix_stats
    return {
        "metric": "serving_prefix_cache_ttft_seconds",
        "value": round(ttft_on, 5),
        "unit": "seconds",
        "vs_baseline": round(ttft_on / ttft_off, 3),
        "extra": {"device_kind": kind, "requests": batch,
                  "sys_prompt_tokens": sys_len,
                  "suffix_tokens": sfx_len, "page_size": page,
                  "ttft_seconds_sharing_off": round(ttft_off, 5),
                  "ttft_seconds_sharing_on": round(ttft_on, 5),
                  "ttft_speedup": round(ttft_off / ttft_on, 3),
                  "pages_after_admission_sharing_off": pages_off,
                  "pages_after_admission_sharing_on": pages_on,
                  "capacity_ratio": round(pages_off / pages_on, 3),
                  "prefix_hit_rate": round(
                      st["hit_tokens"] /
                      (st["hit_tokens"] + st["miss_tokens"]), 3),
                  "shared_pages_mapped": st["shared_pages"],
                  "prefill_compiles": LLMEngine.prefill_compiles(),
                  "decode_compiles": LLMEngine.decode_compiles()}}


def bench_serving_sched():
    """Serving-scheduler row (ISSUE 4): GOODPUT — tokens delivered
    within their deadline per wall second — under an overload burst
    (demand > slot/page capacity), continuous-batching ``Scheduler``
    vs the naive FIFO admit-until-OOM loop every caller hand-rolled
    before the serving subsystem existed.  The naive loop burns wall
    time decoding requests that can no longer meet their deadline and
    discovers capacity by CATCHING the paged cache's OOM raise; the
    scheduler admission-checks capacity (zero OOM events) and sheds
    waiting requests whose deadline already passed.  The deadline is
    calibrated in-process to half the naive full-burst wall time, so
    the comparison is honest on any chip."""
    import paddle_tpu as paddle
    from paddle_tpu.common.errors import EnforceError
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        seqs, page, maxlen = 8, 128, 2048
        burst, plen, new = 32, 256, 128
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        seqs, page, maxlen = 4, 8, 32
        burst, plen, new = 16, 6, 16
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    reqs = [(f"r{i}", rng.integers(1, cfg.vocab_size, plen).tolist())
            for i in range(burst)]

    def engine():
        # n_pages defaults to full per-slot budget: demand (burst) is
        # burst/seqs times the slot capacity -> a true overload
        return LLMEngine(model, max_seqs=seqs, max_len=maxlen,
                         page_size=page, dtype=dtype,
                         enable_prefix_caching=False)

    def run_naive(deadline):
        """FIFO admit-until-OOM: the pre-subsystem caller loop."""
        eng = engine()
        pend = list(reqs)
        finish = {}
        ooms = 0
        t0 = time.perf_counter()
        while pend or eng.has_work():
            while pend:
                rid, prompt = pend[0]
                try:
                    eng.add_request(rid, prompt, max_new_tokens=new)
                except EnforceError:
                    ooms += 1                 # slot/page capacity full
                    break
                pend.pop(0)
            if pend and not eng.has_work():
                break                         # head request can't ever fit
            eng.step()
            now = time.perf_counter()
            for rid, req in eng.requests.items():
                if req.done and rid not in finish:
                    finish[rid] = now
        wall = time.perf_counter() - t0
        ontime = sum(len(eng.result(rid)) for rid, t in finish.items()
                     if t - t0 <= deadline)
        return ontime / wall, wall, ontime, ooms

    def run_sched(deadline):
        eng = engine()
        sched = Scheduler(eng, max_queue=burst)
        t0 = time.perf_counter()
        for rid, prompt in reqs:
            sched.submit(rid, prompt, max_new_tokens=new,
                         deadline=deadline)
        sched.run_until_idle()
        wall = time.perf_counter() - t0
        ontime = sum(len(rec.tokens) for rec in sched._reqs.values()
                     if rec.state == "finished"
                     and not rec.deadline_missed)
        return (ontime / wall, wall, ontime,
                int(eng.cache.metrics_snapshot()["oom_events"]),
                dict(sched.shed_stats))

    run_naive(float("inf"))                   # warmup: compiles
    _, t_full, _, _ = run_naive(float("inf"))
    deadline = t_full / 2
    g_naive, w_naive, tok_naive, ooms_naive = run_naive(deadline)
    g_sched, w_sched, tok_sched, ooms_sched, shed = run_sched(deadline)
    return {
        "metric": "serving_sched_goodput_tokens_per_sec",
        "value": round(g_sched, 1),
        "unit": "tokens/sec (within deadline)",
        "vs_baseline": round(g_sched / g_naive, 3) if g_naive else None,
        "extra": {"device_kind": kind, "burst_requests": burst,
                  "slots": seqs, "max_new_tokens": new,
                  "deadline_seconds": round(deadline, 4),
                  "goodput_naive_fifo": round(g_naive, 1),
                  "wall_seconds_naive": round(w_naive, 4),
                  "wall_seconds_sched": round(w_sched, 4),
                  "ontime_tokens_naive": tok_naive,
                  "ontime_tokens_sched": tok_sched,
                  "oom_raises_caught_naive": ooms_naive,
                  "oom_events_sched": ooms_sched,
                  "shed": shed}}


def bench_serving_preempt():
    """Preemptive-scheduling row (ISSUE 5): priority-mixed OVERLOAD —
    low-priority long decodes saturate every slot, then high-priority
    short requests arrive.  The PR 4 scheduler (``preemption=False``)
    parks the high-priority work until a long decode finishes its full
    token budget; the preemptive scheduler suspends the
    lowest-priority active request (KV pages swap to the host pool),
    admits the high-priority request into the freed slot NOW, and
    resumes the victim afterwards with bit-identical tokens.  Headline
    value: mean high-priority TTFT (submit → first token).  Goodput
    (total tokens / wall) is reported too — preemption must not buy
    latency with meaningful throughput (the swap/replay overhead is
    the only tax)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        seqs, page, maxlen = 4, 128, 2048
        n_low, n_high, plen, new_low, new_high = 4, 4, 256, 512, 32
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        seqs, page, maxlen = 2, 8, 32
        n_low, n_high, plen, new_low, new_high = 2, 2, 4, 24, 4
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    lows = [(f"lo{i}", rng.integers(1, cfg.vocab_size, plen).tolist())
            for i in range(n_low)]
    highs = [(f"hi{i}", rng.integers(1, cfg.vocab_size, plen).tolist())
             for i in range(n_high)]

    def run(preempt):
        eng = LLMEngine(model, max_seqs=seqs, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        enable_prefix_caching=False)
        sched = Scheduler(eng, max_queue=n_low + n_high,
                          preemption=preempt,
                          max_preemptions_per_request=4)
        submit_t, ttft = {}, {}

        def watch(rid):
            def cb(ev):
                if ev["type"] == "tokens" and rid not in ttft:
                    ttft[rid] = time.perf_counter() - submit_t[rid]
            return cb

        t0 = time.perf_counter()
        for rid, prompt in lows:
            submit_t[rid] = time.perf_counter()
            sched.submit(rid, prompt, max_new_tokens=new_low,
                         priority=1, on_event=watch(rid))
        sched.step()                          # longs take every slot
        for rid, prompt in highs:
            submit_t[rid] = time.perf_counter()
            sched.submit(rid, prompt, max_new_tokens=new_high,
                         priority=0, on_event=watch(rid))
        sched.run_until_idle()
        wall = time.perf_counter() - t0
        tokens = sum(len(rec.tokens) for rec in sched._reqs.values()
                     if rec.state == "finished")
        hi_ttft = float(np.mean([ttft[r] for r, _ in highs]))
        snap = sched.metrics_snapshot()
        return (hi_ttft, tokens / wall, wall,
                snap.get("preempted", 0),
                int(snap["engine"]["kv_cache"]["oom_events"]),
                snap["engine"]["kv_cache"]["swap_out_pages"])

    run(True)                                 # warmup: compiles
    base_ttft, base_goodput, base_wall, _, base_oom, _ = run(False)
    pre_ttft, pre_goodput, pre_wall, n_preempt, pre_oom, swapped = \
        run(True)
    return {
        "metric": "serving_preempt_high_priority_ttft_seconds",
        "value": round(pre_ttft, 4),
        "unit": "seconds (mean, high priority)",
        "vs_baseline": round(base_ttft / pre_ttft, 3) if pre_ttft
        else None,
        "extra": {"device_kind": kind, "slots": seqs,
                  "low_priority_requests": n_low,
                  "high_priority_requests": n_high,
                  "max_new_low": new_low, "max_new_high": new_high,
                  "ttft_no_preemption": round(base_ttft, 4),
                  "goodput_preempt_tok_per_s": round(pre_goodput, 1),
                  "goodput_no_preempt_tok_per_s":
                      round(base_goodput, 1),
                  "wall_seconds_preempt": round(pre_wall, 4),
                  "wall_seconds_no_preempt": round(base_wall, 4),
                  "preemptions": n_preempt,
                  "swapped_out_pages": swapped,
                  "oom_events": pre_oom + base_oom}}


def bench_serving_drain():
    """Fault-tolerant multi-host row (ISSUE 6): drain a replica with
    in-flight decodes and resume them on a second replica.  The
    KV-MIGRATING drain ships each request's swap pages (serialized
    blob) and swap-ins at the destination; the baseline (swap pools
    disabled) must RECOMPUTE — replay the prompt through chunked
    prefill and every generated token through the decode program.
    Headline value: wall seconds from drain start to all drained
    requests finished, migration path; vs_baseline is the recompute
    path's wall on the same schedule.  Both paths must land
    bit-identical tokens and lose zero requests — the bench asserts
    it."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ReplicaRouter, Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        seqs, page, maxlen = 4, 128, 2048
        n_req, plen, n_new, warm_steps = 4, 256, 512, 256
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        seqs, page, maxlen = 4, 8, 64
        n_req, plen, n_new, warm_steps = 3, 4, 32, 16
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = {f"d{i}": rng.integers(1, cfg.vocab_size, plen).tolist()
               for i in range(n_req)}

    def reference(rid):
        eng = LLMEngine(model, max_seqs=seqs, max_len=maxlen,
                        page_size=page, dtype=dtype)
        eng.add_request("ref", prompts[rid], max_new_tokens=n_new)
        while eng.has_work():
            eng.step()
        return eng.result("ref")

    want = {rid: reference(rid) for rid in prompts}

    def run(swap_pool):
        engines = [LLMEngine(model, max_seqs=seqs, max_len=maxlen,
                             page_size=page, dtype=dtype,
                             swap_pool_pages=swap_pool)
                   for _ in range(2)]
        router = ReplicaRouter(
            [Scheduler(e, max_queue=n_req + 1) for e in engines],
            sleep=lambda s: None)
        for rid, prompt in prompts.items():
            router.submit(rid, prompt, max_new_tokens=n_new)
        src = router._owner[next(iter(prompts))]
        for _ in range(warm_steps):           # build decode history
            router.replicas[src].step()
        t0 = time.perf_counter()
        moved = router.drain_replica(src)
        router.run_until_idle()
        wall = time.perf_counter() - t0
        lost = [rid for rid in prompts
                if router.pop_result(rid) != want[rid]]
        assert not lost, f"drain lost/corrupted requests: {lost}"
        dst_cache = engines[1 - src].cache.metrics_snapshot()
        return wall, len(moved), dst_cache

    run(None)                                 # warmup: compiles
    mig_wall, mig_moved, mig_cache = run(None)     # swap pools on
    rec_wall, rec_moved, rec_cache = run(0)        # recompute only
    return {
        "metric": "serving_drain_migration_seconds",
        "value": round(mig_wall, 4),
        "unit": "seconds (drain -> all drained requests finished)",
        "vs_baseline": round(rec_wall / mig_wall, 3) if mig_wall
        else None,
        "extra": {"device_kind": kind, "replicas": 2,
                  "requests_moved": mig_moved,
                  "prompt_tokens": plen, "max_new_tokens": n_new,
                  "decode_steps_before_drain": warm_steps,
                  "wall_seconds_recompute": round(rec_wall, 4),
                  "swap_in_pages_migration":
                      mig_cache["swap_in_pages"],
                  "swap_imported_pages_migration":
                      mig_cache["swap_imported_pages"],
                  "swap_in_pages_recompute":
                      rec_cache["swap_in_pages"],
                  "lost_requests": 0}}


def jnp_bf16():
    import jax.numpy as jnp
    return jnp.bfloat16


def bench_ckpt():
    """Crash-safe training row (ISSUE 7): checkpoint overhead on the
    compiled training step — atomic staging commit + per-chunk sha256,
    saved every K steps through a CheckpointManager.  Headline value:
    async-save wall overhead vs a no-checkpoint run of the same steps
    (1.0 = free); vs_baseline is the SYNC overhead on the same schedule
    — the gap is what the bounded write-behind queue buys.  The bench
    asserts the last checkpoint validates (committed manifest, sha256)
    so the speed is never bought with a torn save."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.distributed.checkpoint import validate_checkpoint
    from paddle_tpu.distributed.ckpt_manager import CheckpointManager
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTPretrainingCriterion,
                                       gpt2_tiny_config)

    _, kind, peak, hbm, on_tpu = _device()
    cfg = gpt2_tiny_config()
    rng = np.random.default_rng(0)
    ids = ((np.arange(32)[None, :] + rng.integers(0, 8, (8, 1))) % 32
           ).astype(np.int32)
    batch = {"x": ids[:, :-1], "y": ids[:, 1:].astype(np.int64)}
    steps, save_every = 12, 3

    def make_step():
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01)
        return CompiledTrainStep(
            model, lambda m, b: crit(m(b["x"]), b["y"]), opt, seed=0)

    def run(mode, root):
        step = make_step()
        manager = None if mode == "none" else CheckpointManager(
            root, keep_last_n=2, async_save=(mode == "async"))
        loss = step(batch)                       # compile outside timing
        import jax
        jax.device_get(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step(batch)
            if manager is not None and (i + 1) % save_every == 0:
                manager.save(step, i + 1)
        if manager is not None:
            manager.wait()                       # async saves must land
        jax.device_get(loss)
        wall = time.perf_counter() - t0
        if manager is not None:
            validate_checkpoint(manager.step_dir(steps))
        return wall

    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        run("none", root)                        # warm the whole path
        base = run("none", root)
        sync_w = run("sync", os.path.join(root, "s"))
        async_w = run("async", os.path.join(root, "a"))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    from paddle_tpu.observability import get_registry
    hist = get_registry().get("ckpt_save_seconds")
    means = {m: round(hist.labels(m).mean, 4)
             for m in ("sync", "async")} if hist is not None else {}
    return {
        "metric": "ckpt_async_step_overhead",
        "value": round(async_w / base, 4),
        "unit": "x wall vs no-checkpoint run (1.0 = free)",
        "vs_baseline": round(sync_w / base, 4),
        "extra": {"device_kind": kind, "steps": steps,
                  "save_every": save_every,
                  "wall_none_s": round(base, 4),
                  "wall_sync_s": round(sync_w, 4),
                  "wall_async_s": round(async_w, 4),
                  "save_seconds_mean": means}}


def bench_train_fused():
    """Fused-step-regions row (BENCH_r08): fused vs unfused compiled
    train step.  On TPU the fused path runs the one-pass Pallas
    clip+optimizer kernel (small-leaf tail packed into one launch) plus
    the add+RMSNorm and matmul+rope chains at the headline ladder pick;
    the MFU delta toward the ROADMAP >=0.55 target is the headline.
    Off TPU there is no Pallas: both paths lower to STRUCTURALLY
    IDENTICAL XLA programs (that is the bit-identity contract
    tests/test_fused_train.py pins), so the CPU fallback at the tiny
    ladder config validates parity — the honest expectation is a ratio
    ~1.0x, measured with interleaved best-of reps so the 1-core box's
    scheduling noise cannot manufacture a fake win either way."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    dev, kind, peak, hbm, on_tpu = _device()
    seq = _SEQ if on_tpu else 128
    if on_tpu:
        name, h, i, layers, heads, kv, batch, n_params = _pick_config(
            hbm, seq)
    else:
        # llama-tiny geometry (the budget-guard-pinned CPU fallback)
        name, h, i, layers, heads, kv, batch = \
            "llama-tiny", 256, 512, 4, 8, 4, 4
    cfg = LlamaConfig(
        vocab_size=_VOCAB if on_tpu else 1024, hidden_size=h,
        intermediate_size=i, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv,
        max_position_embeddings=seq, recompute=on_tpu,
        recompute_granularity="core_attn")
    n_params = _param_count(h, i, layers, heads, kv, cfg.vocab_size)

    def build(fused):
        paddle.seed(12)
        model = LlamaForCausalLM(cfg)
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-4, parameters=model.parameters(),
            grad_clip=paddle.ClipGradByGlobalNorm(1.0))
        return CompiledTrainStep(
            model, lambda m, b: m(b["input_ids"], labels=b["labels"]),
            opt, fused_step=fused)

    data = _train_batch(cfg.vocab_size, batch, seq)
    steps = {"fused": build(True), "unfused": build(False)}
    for s in steps.values():                      # compile + settle
        jax.device_get(s(data))
        jax.device_get(s(data))
    iters = 10 if on_tpu else 6
    reps = 3 if on_tpu else 5
    best = {k: float("inf") for k in steps}
    for _ in range(reps):
        for label, s in steps.items():            # interleaved best-of
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = s(data)
            jax.device_get(loss)
            best[label] = min(best[label],
                              (time.perf_counter() - t0) / iters)
    tps = batch * seq / best["fused"]
    mfu_f, mfu_fa = _mfu_pair(n_params, layers, h, seq, tps, peak)
    mfu_u, _ = _mfu_pair(n_params, layers, h, seq,
                         batch * seq / best["unfused"], peak)
    speedup = best["unfused"] / best["fused"]
    return {
        "metric": f"{name}_fused_step_speedup",
        "value": round(speedup, 4),
        "unit": "x unfused step time (>1 = fused faster)",
        "vs_baseline": round(mfu_f / 0.55, 4) if mfu_f else None,
        "extra": {"device_kind": kind, "params": n_params,
                  "batch": batch, "seq": seq,
                  "step_ms_fused": round(best["fused"] * 1e3, 2),
                  "step_ms_unfused": round(best["unfused"] * 1e3, 2),
                  "mfu_fused": round(mfu_f, 4) if mfu_f else None,
                  "mfu_unfused": round(mfu_u, 4) if mfu_u else None,
                  "mfu_attn_fused": round(mfu_fa, 4) if mfu_fa else None,
                  "mfu_target": 0.55,
                  "kernels_active": bool(on_tpu),
                  "note": ("cpu fallback: fused==unfused programs "
                           "(bit-identity), parity expected"
                           if not on_tpu else
                           "pallas fused clip+update kernel + "
                           "add+norm/matmul+rope chains")},
    }


def bench_longseq():
    """Long-context row: 32k-token sequences on ONE chip (flash attention
    + selective remat + fused CE keep the S^2 and vocab terms off HBM).
    Multi-chip context parallelism (ring/Ulysses over sep) is validated
    functionally in tests/test_context_parallel.py; this row evidences
    the single-chip long-seq capability envelope (SURVEY.md §5
    long-context)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit.train import CompiledTrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    dev, kind, peak, hbm, on_tpu = _device()
    seq = 32768 if on_tpu else 512
    h, i, layers, heads, kv = 1024, 4096, 12, 8, 4       # llama-410m
    # 410M @ 32k fits v5e HBM without remat (measured r3: 21.4k tok/s
    # vs 20.9k with flash-aware core_attn remat vs 17.5k with r2's full
    # remat); larger models should use recompute_granularity="core_attn"
    # — the round-3 policy saves (flash_out, flash_lse) so backward
    # never re-runs the attention kernel
    cfg = LlamaConfig(vocab_size=_VOCAB if on_tpu else 512, hidden_size=h,
                      intermediate_size=i, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=kv,
                      max_position_embeddings=seq, recompute=False)
    model = paddle.amp.decorate(LlamaForCausalLM(cfg), level="O2",
                                dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, b: m(b["input_ids"],
                                                   labels=b["labels"]), opt)
    data = _train_batch(cfg.vocab_size, 1, seq)
    step_time, loss = _time_step(step, data, 10 if on_tpu else 2)
    n = _param_count(h, i, layers, heads, kv, cfg.vocab_size)
    tps = seq / step_time
    mfu6n, mfu_attn = _mfu_pair(n, layers, h, seq, tps, peak)
    return {"metric": "llama-410m_seq32k_tokens_per_sec_per_chip",
            "unit": "tokens/sec", "value": round(tps, 1),
            "extra": {"device_kind": kind, "seq": seq, "batch": 1,
                      "params": n,
                      "mfu": round(mfu6n, 4) if mfu6n else None,
                      "mfu_attn": round(mfu_attn, 4) if mfu_attn else None,
                      "final_loss": float(np.asarray(jax.device_get(loss)))}}


def bench_serving_ragged():
    """Ragged-unified-step row (ISSUE 12): decode latency under a
    long-prompt + decode-heavy overload mix.  The split-program engine
    prefills an admitted prompt synchronously (chunk dispatches back
    to back), stalling every in-flight decode for the whole prompt —
    the head-of-line problem ROADMAP open item 2 named.  The ragged
    unified step packs the prompt's chunks INTO the decode batch (one
    compiled mixed program, per-sequence descriptors as traced
    scalars), so decode token inter-arrival stays near pure-decode
    TPOT while the prefill streams through.  Headline: p99 decode
    TPOT ratio split/unified (>1 = unified absorbs the prefill burst
    better); tokens stay bit-identical (tests/test_ragged_mixed.py
    pins that), so this row is pure scheduling latency.  Interleaved
    best-of reps keep 1-core scheduling noise honest."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Scheduler

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=4096)
        seqs, page, maxlen = 8, 128, 4096
        n_dec, new_dec = 6, 160
        n_long, plen_long, new_long = 2, 1536, 16
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        seqs, page, maxlen = 4, 8, 64
        n_dec, new_dec = 3, 24
        n_long, plen_long, new_long = 1, 40, 4
        dtype = np.float32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    dec_prompts = [rng.integers(1, cfg.vocab_size, 4).tolist()
                   for _ in range(n_dec)]
    long_prompts = [rng.integers(1, cfg.vocab_size, plen_long).tolist()
                    for _ in range(n_long)]

    def run(unified):
        eng = LLMEngine(model, max_seqs=seqs, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        enable_prefix_caching=False,
                        unified_step=unified)
        sched = Scheduler(eng, max_queue=64, chunked_prefill=unified)
        arriv = {}
        for i, p in enumerate(dec_prompts):
            sched.submit(f"d{i}", p, max_new_tokens=new_dec)
            arriv[f"d{i}"] = []
        submitted = False
        t0 = time.perf_counter()
        while sched.busy():
            out = sched.step()
            now = time.perf_counter()
            for rid, toks in out.items():
                if rid in arriv:
                    arriv[rid].extend([now] * len(toks))
            if not submitted and arriv and \
                    min(len(a) for a in arriv.values()) >= 3:
                # every decode is mid-stream: NOW the prompt arrives
                for j in range(n_long):
                    sched.submit(f"L{j}", long_prompts[j],
                                 max_new_tokens=new_long)
                submitted = True
        wall = time.perf_counter() - t0
        total = sum(len(sched.result(f"d{i}")) for i in range(n_dec))
        total += sum(len(sched.result(f"L{j}")) for j in range(n_long))
        gaps = np.concatenate([np.diff(np.asarray(a))
                               for a in arriv.values() if len(a) > 1])
        return total / wall, gaps

    for uni in (False, True):
        run(uni)                                  # warmup: compiles
    reps = 2 if on_tpu else 3
    best = {}
    for _ in range(reps):
        for label, uni in (("split", False), ("unified", True)):
            tps, gaps = run(uni)                  # interleaved best-of
            if label not in best or tps > best[label][0]:
                best[label] = (tps, gaps)
    p = {label: {q: float(np.percentile(g, q) * 1e3)
                 for q in (50, 99)}
         for label, (_, g) in best.items()}
    ratio = p["split"][99] / p["unified"][99]
    return {
        "metric": "llama_serving_ragged_p99_decode_tpot_ratio",
        "value": round(ratio, 3),
        "unit": "x split-program p99 decode TPOT (>1 = unified "
                "absorbs concurrent prefill better)",
        "extra": {"device_kind": kind, "decode_slots": n_dec,
                  "decode_new_tokens": new_dec,
                  "long_prompts": n_long, "long_prompt_len": plen_long,
                  "prefill_token_budget": page,
                  "tpot_p50_ms_split": round(p["split"][50], 3),
                  "tpot_p99_ms_split": round(p["split"][99], 3),
                  "tpot_p50_ms_unified": round(p["unified"][50], 3),
                  "tpot_p99_ms_unified": round(p["unified"][99], 3),
                  "tokens_per_sec_split": round(best["split"][0], 1),
                  "tokens_per_sec_unified": round(best["unified"][0], 1),
                  "mixed_compiles": LLMEngine.mixed_compiles(),
                  "prefill_compiles": LLMEngine.prefill_compiles(),
                  "decode_compiles": LLMEngine.decode_compiles()}}


def verify_dropout_smoke():
    """TPU-only dropout numerics smoke (VERDICT r3 Weak #6): the twin
    of the two CPU-perma-skipped tests in tests/test_pallas_flash.py
    (interpret mode stubs prng_random_bits) — deterministic per seed,
    seed-sensitive, actually drops, mean-preserving across seeds."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_raw

    _, kind, _, _, on_tpu = _device()
    if not on_tpu:
        return {"verify": "dropout_smoke", "ok": False,
                "note": "tpu_only"}
    rng = np.random.default_rng(5)
    b, s, h, d = 1, 256, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)

    def run(seed, p=0.5):
        return np.asarray(jax.jit(
            lambda q, k, v: flash_attention_raw(
                q, k, v, causal=False, dropout_p=p,
                seed=jnp.int32(seed)))(q, k, v))

    o1, o2 = run(42), run(42)
    deterministic = bool(np.array_equal(o1, o2))
    seed_sensitive = float(np.abs(o1 - run(7)).max()) > 1e-3
    base = np.asarray(jax.jit(
        lambda q, k, v: flash_attention_raw(q, k, v, causal=False))(
        q, k, v))
    drops = float(np.abs(o1 - base).max()) > 1e-3
    avg = sum(run(i).astype(np.float64) for i in range(16)) / 16
    mean_err = float(np.abs(avg - base).mean() / np.abs(base).mean())
    ok = deterministic and seed_sensitive and drops and mean_err < 0.35
    return {"verify": "dropout_smoke", "ok": bool(ok),
            "extra": {"device_kind": kind,
                      "deterministic": deterministic,
                      "seed_sensitive": bool(seed_sensitive),
                      "drops": bool(drops),
                      "mean_err": round(mean_err, 4)}}


def bench_serving_tp():
    """Sharded-serving row (ISSUE 18): the same staggered greedy
    workload through a tp=1 engine and a tp=2 tensor-parallel engine
    over a GSPMD mesh (forced-host CPU devices off-TPU, real chips on).
    The sharding discipline constrains only OUTPUT axes and gathers
    every contraction input first, so the row asserts tokens are
    BIT-IDENTICAL across tp — sharding is a pure capacity/latency
    lever, never a numerics knob.  Also measured: the one-compile
    invariant per mesh shape (a second tp=2 engine must add zero
    mixed/window compiles) and the per-chip KV-pool bytes from
    ``memory_rows()``.  Headline: the per-chip KV capacity multiplier
    of tp=2 + int8 KV over the tp=1 fp32 pool — the two levers
    (head-sharding the pools, per-token int8) multiply instead of
    fighting, which is the point of keeping the scale pools on the
    same KVH sharding."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import serving_mesh
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    if on_tpu:
        cfg = LlamaConfig(vocab_size=_VOCAB, hidden_size=1536,
                          intermediate_size=6144, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=2048)
        batch, new, page, maxlen, sync = 8, 128, 128, 2048, 16
        prompts = [96, 57, 128, 101, 77, 120, 64, 115]
        dtype = jnp_bf16()
    else:
        from paddle_tpu.models.llama import llama_tiny_config
        cfg = llama_tiny_config()
        batch, new, page, maxlen, sync = 4, 48, 8, 128, 4
        prompts = [8, 5, 12, 9]
        dtype = np.float32
    ndev = len(jax.devices())
    if ndev < 2:
        return {"metric": "llama_serving_tp_kv_per_chip_multiplier",
                "unit": "x", "value": 1.0,
                "extra": {"device_kind": kind, "note":
                          "single device — no tp mesh (run tests "
                          "under the forced 8-device CPU platform)"}}
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def run(mesh, **kw):
        rng = np.random.default_rng(0)
        eng = LLMEngine(model, max_seqs=batch, max_len=maxlen,
                        page_size=page, dtype=dtype,
                        steps_per_sync=sync, unified_step=True,
                        mesh=mesh, **kw)
        for i, plen in enumerate(prompts):
            eng.add_request(
                f"t{i}", rng.integers(1, cfg.vocab_size, plen).tolist(),
                max_new_tokens=new)
            eng.step()                 # staggered: batches churn
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        toks = {f"t{i}": eng.result(f"t{i}")
                for i in range(len(prompts))}
        produced = sum(len(v) for v in toks.values())
        return eng, toks, produced / dt

    mesh2 = serving_mesh(2)
    eng1, want, rate1 = run(None)
    eng2, got, rate2 = run(mesh2)
    bit_identical = got == want
    base_m = LLMEngine.mixed_compiles()
    base_w = LLMEngine.window_compiles()
    run(mesh2)                         # second tp=2 engine, same mesh
    mixed_delta = LLMEngine.mixed_compiles() - base_m
    window_delta = LLMEngine.window_compiles() - base_w

    rows1 = eng1.cache.memory_rows()             # tp=1 fp32 pool
    eng_i8, _, _ = run(mesh2, kv_dtype="int8")
    rows_i8 = eng_i8.cache.memory_rows()         # tp=2 int8 + scales
    per_chip_fp1 = rows1["device_bytes_per_shard"]
    per_chip_i8tp2 = rows_i8["device_bytes_per_shard"]
    mult = per_chip_fp1 / max(per_chip_i8tp2, 1)
    return {"metric": "llama_serving_tp_kv_per_chip_multiplier",
            "unit": "x", "value": round(mult, 2),
            "extra": {"device_kind": kind, "tp": 2,
                      "bit_identical_tp1_vs_tp2": bit_identical,
                      "mixed_compile_delta_same_mesh": mixed_delta,
                      "window_compile_delta_same_mesh": window_delta,
                      "tokens_per_sec_tp1": round(rate1, 1),
                      "tokens_per_sec_tp2": round(rate2, 1),
                      "kv_bytes_per_chip_tp1_fp32": per_chip_fp1,
                      "kv_bytes_per_chip_tp2_int8": per_chip_i8tp2,
                      "budget": "bit_identical AND zero compile "
                                "delta on a warm mesh shape"}}


def bench_serving_moe():
    """MoE serving row (ISSUE 19): the same staggered greedy workload
    through a Qwen2-MoE engine with grouped-matmul dispatch (ONE
    grouped_matmul per layer over expert-sorted rows) vs the dense
    per-expert reference, at 8 and at 64 experts.  Rates are
    interleaved best-of-3 on WARM engines (both dispatch modes
    measured in alternation so ambient noise hits them equally).
    Also recorded: bit-identity between the two dispatch modes at
    each expert count (the acceptance bar — dispatch is a layout
    decision, never a numerics knob) and the mixed-program compile
    delta for a second same-geometry engine (expert descriptors are
    traced data: zero new compiles).  Headline: the grouped/dense
    decode-throughput ratio at 64 experts.  On TPU the grouped path
    feeds ONE MXU grouped_matmul kernel and should pull ahead of the
    dense reference's every-expert-for-every-row compute; on CPU
    both modes run the gathered-einsum reference, so grouping pays
    sort + tile-padding overhead with nothing to buy it back and the
    ratio lands BELOW 1 — the budget for this row is the numerics
    (bit-identity) and the compile invariant, not the CPU ratio."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)

    _, kind, peak, hbm, on_tpu = _device()
    batch, new, page, maxlen, sync = 4, 32, 8, 128, 4
    prompts = [8, 5, 12, 9]
    reps = 3

    def mk_cfg(e):
        return Qwen2MoeConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            moe_intermediate_size=32,
            shared_expert_intermediate_size=64,
            num_experts=e, num_experts_per_tok=2,
            max_position_embeddings=maxlen)

    def serve(eng, tag):
        rng = np.random.default_rng(0)
        for i, plen in enumerate(prompts):
            eng.add_request(
                f"{tag}_{i}", rng.integers(1, 256, plen).tolist(),
                max_new_tokens=new)
            eng.step()                 # staggered: batches churn
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        toks = [eng.result(f"{tag}_{i}")
                for i in range(len(prompts))]
        return toks, sum(len(t) for t in toks) / dt

    per_e, compile_delta = {}, None
    for n_experts in (8, 64):
        paddle.seed(0)
        model = Qwen2MoeForCausalLM(mk_cfg(n_experts))
        model.eval()
        engines = {d: LLMEngine(model, max_seqs=batch,
                                max_len=maxlen, page_size=page,
                                steps_per_sync=sync, moe_dispatch=d)
                   for d in ("grouped", "dense")}
        toks = {d: serve(engines[d], f"warm{n_experts}{d}")[0]
                for d in engines}      # warm: compile + first parity
        best = {d: 0.0 for d in engines}
        for rep in range(reps):        # interleaved best-of: noise
            for d, eng in engines.items():   # hits both modes alike
                best[d] = max(best[d],
                              serve(eng, f"r{rep}{n_experts}{d}")[1])
        if n_experts == 8:             # second same-geometry engine:
            base = LLMEngine.mixed_compiles()     # traced descriptors
            serve(LLMEngine(model, max_seqs=batch, max_len=maxlen,
                            page_size=page, steps_per_sync=sync),
                  "again8")            # -> zero new programs
            compile_delta = LLMEngine.mixed_compiles() - base
        per_e[n_experts] = {
            "bit_identical": toks["grouped"] == toks["dense"],
            "tokens_per_sec_grouped": round(best["grouped"], 1),
            "tokens_per_sec_dense": round(best["dense"], 1),
            "ratio": round(best["grouped"] / max(best["dense"], 1e-9),
                           3)}
    return {"metric": "qwen2moe_serving_grouped_vs_dense_speedup_e64",
            "unit": "x", "value": per_e[64]["ratio"],
            "extra": {"device_kind": kind,
                      "experts_8": per_e[8], "experts_64": per_e[64],
                      "top_k": 2, "best_of": reps,
                      "mixed_compile_delta_same_geometry":
                          compile_delta,
                      "budget": "bit_identical at BOTH expert counts "
                                "AND zero compile delta on a warm "
                                "geometry"}}


def bench_serving_spec():
    """Speculative decoding row (ISSUE 20): staggered greedy decode
    through an 8-layer llama target, plain engine (steps_per_sync=4
    on-device window — the repo's strongest non-speculative config)
    vs ``LLMEngine(draft_model=..., spec_k=4)`` with a 1-layer draft.
    Two draft points bound the acceptance sweep: a RANDOM 1-layer
    draft (near-zero agreement — the overhead floor, spec pays
    propose+verify and delivers ~1 token/window) and a DISTILLED
    1-layer draft (residual branches epsilon-scaled in both models,
    embed/head/final-norm shared, so both argmax from the
    embedding-dominated logits — acceptance ≈ 1, the regime a real
    distilled draft buys).  Rates are interleaved best-of-3 on WARM
    engines.  Also recorded: greedy BIT-IDENTITY of the speculative
    stream against plain decode at BOTH acceptance points (the
    tentpole bar — speculation is a latency trick, never a sampler)
    and each point's measured acceptance rate off the engine's own
    counters.  Headline: the spec/plain decode-throughput ratio with
    the distilled draft; budget >1.5x on CPU (one draft-scan dispatch
    + one ragged verify dispatch replace k+1 sequential 8-layer
    steps)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    _, kind, peak, hbm, on_tpu = _device()
    batch, new, page, maxlen, sync, k = 4, 48, 8, 256, 4, 4
    prompts = [8, 5, 12, 9]
    reps = 5
    geo = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               max_position_embeddings=maxlen, rms_norm_eps=1e-5)

    paddle.seed(0)
    target = LlamaForCausalLM(LlamaConfig(num_hidden_layers=8, **geo))
    target.eval()

    def mk_draft(distilled):
        paddle.seed(1)
        d = LlamaForCausalLM(LlamaConfig(num_hidden_layers=1, **geo))
        d.eval()
        if distilled:
            # epsilon-scale the residual-branch outputs in BOTH
            # models and share embed/head/final-norm: logits become
            # embedding-dominated, so the 1-layer draft argmaxes with
            # the 8-layer target almost always — a stand-in for a
            # distillation run this bench can't afford
            for m in (target, d):
                for layer in m.llama.layers:
                    for lin in (layer.self_attn.o_proj,
                                layer.mlp.down_proj):
                        lin.weight.set_value(
                            np.asarray(lin.weight.value) * 1e-3)
            sd = target.state_dict()
            for dst, key in [(d.llama.embed_tokens,
                              "llama.embed_tokens.weight"),
                             (d.llama.norm, "llama.norm.weight"),
                             (d.lm_head, "lm_head.weight")]:
                dst.weight.set_value(np.asarray(sd[key]))
        return d

    def serve(eng, tag):
        rng = np.random.default_rng(0)
        for i, plen in enumerate(prompts):
            eng.add_request(
                f"{tag}_{i}", rng.integers(1, 256, plen).tolist(),
                max_new_tokens=new)
            eng.step()                 # staggered: batches churn
        t0 = time.perf_counter()
        while eng.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        toks = [eng.result(f"{tag}_{i}")
                for i in range(len(prompts))]
        return toks, sum(len(t) for t in toks) / dt

    points = {}
    # random draft FIRST: mk_draft(True) mutates the shared target
    for name, distilled in (("random_draft", False),
                            ("distilled_draft", True)):
        draft = mk_draft(distilled)
        plain = LLMEngine(target, max_seqs=batch, max_len=maxlen,
                          page_size=page, steps_per_sync=sync)
        spec = LLMEngine(target, max_seqs=batch, max_len=maxlen,
                         page_size=page, draft_model=draft, spec_k=k)
        pt, _ = serve(plain, f"w_{name}_p")   # warm: compile parity
        st, _ = serve(spec, f"w_{name}_s")
        best_p = best_s = 0.0
        for rep in range(reps):        # interleaved best-of: noise
            best_p = max(best_p,       # hits both engines alike
                         serve(plain, f"p{rep}{name}")[1])
            best_s = max(best_s, serve(spec, f"s{rep}{name}")[1])
        s = spec.metrics_snapshot()["spec"]
        points[name] = {
            "bit_identical": pt == st,
            "acceptance_rate": round(s["acceptance_rate"], 3),
            "tokens_per_sec_plain": round(best_p, 1),
            "tokens_per_sec_spec": round(best_s, 1),
            "ratio": round(best_s / max(best_p, 1e-9), 3)}
    return {"metric": "serving_spec_decode_speedup_distilled_draft",
            "unit": "x", "value": points["distilled_draft"]["ratio"],
            "extra": {"device_kind": kind, "spec_k": k,
                      "target_layers": 8, "draft_layers": 1,
                      "plain_steps_per_sync": sync, "best_of": reps,
                      "random_draft": points["random_draft"],
                      "distilled_draft": points["distilled_draft"],
                      "budget": "bit_identical at BOTH acceptance "
                                "points AND distilled ratio > 1.5x "
                                "on CPU"}}


def bench_history(root=None, emit=True):
    """Fold every ``BENCH_rNN.json`` snapshot (the driver's one-file-
    per-round bench record) into ONE trajectory table: a row per
    (round, metric) with value, unit, and the delta (percent) against
    the SAME metric's most recent earlier round — how each headline
    number moved across the PR sequence, read from the repo itself.
    Tail lines that are not metric JSON (platform WARNINGs, *_ERROR
    rows) are skipped tolerantly; a malformed snapshot file skips
    whole, never aborts the fold.  Prints the table plus one summary
    JSON line (``emit=True``) and returns the full structure."""
    import glob
    import re
    root = root or os.path.dirname(os.path.abspath(__file__))
    files = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = re.match(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if m:
            files.append((int(m.group(1)), path))
    rows, last = [], {}
    for rnd, path in sorted(files):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        for line in (rec.get("tail") or "").splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue                       # platform WARNING noise
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            metric = obj.get("metric")
            if not metric or metric.endswith("_ERROR") or \
                    "value" not in obj:
                continue
            value = obj["value"]
            delta = None
            prev = last.get(metric)
            if isinstance(value, (int, float)) and \
                    prev not in (None, 0):
                delta = round((value - prev) / abs(prev) * 100, 2)
            rows.append({"round": rnd, "metric": metric,
                         "value": value, "unit": obj.get("unit"),
                         "delta_pct": delta})
            if isinstance(value, (int, float)):
                last[metric] = value
    out = {"metric": "bench_history", "unit": "rows",
           "value": len(rows),
           "rounds": sorted({r["round"] for r in rows}),
           "metrics": sorted(last), "rows": rows}
    if emit:
        w = max([len(r["metric"]) for r in rows] or [6])
        print(f"{'round':>5}  {'metric':<{w}}  {'value':>12}  "
              f"{'delta%':>8}  unit")
        for r in rows:
            d = "" if r["delta_pct"] is None \
                else f"{r['delta_pct']:+.2f}"
            print(f"{r['round']:>5}  {r['metric']:<{w}}  "
                  f"{r['value']:>12}  {d:>8}  {r['unit'] or ''}")
        print(json.dumps({k: v for k, v in out.items()
                          if k != "rows"}))
    return out


def main():
    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--verify" in sys.argv:
        res = verify_dropout_smoke()
        print(json.dumps(res))
        if res.get("note") == "tpu_only":
            sys.exit(86)        # skip: no TPU — not a numerics failure
        sys.exit(0 if res["ok"] else 1)
    if "--history" in sys.argv:
        bench_history()
        return 0
    if "--ladder" in sys.argv:
        # stream each row as it completes: an error in one row must
        # not lose the rows already measured
        fns = [("bench_headline", lambda: bench_headline(emit=False)),
               ("bench_gpt2", bench_gpt2), ("bench_ernie", bench_ernie),
               ("bench_dit", bench_dit), ("bench_moe", bench_moe),
               ("bench_decode", bench_decode),
               ("bench_moe_deepseek", bench_moe_deepseek),
               ("bench_paged_kernel", bench_paged_kernel),
               ("bench_engine", bench_engine),
               ("bench_serving_quant", bench_serving_quant),
               ("bench_serving_metrics", bench_serving_metrics),
               ("bench_trace", bench_trace),
               ("bench_fleet_health", bench_fleet_health),
               ("bench_introspection", bench_introspection),
               ("bench_serving_prefix", bench_serving_prefix),
               ("bench_serving_sched", bench_serving_sched),
               ("bench_serving_preempt", bench_serving_preempt),
               ("bench_serving_drain", bench_serving_drain),
               ("bench_serving_ragged", bench_serving_ragged),
               ("bench_ckpt", bench_ckpt),
               ("bench_train_fused", bench_train_fused),
               ("bench_engine_window", bench_engine_window),
               ("bench_decode_window", bench_decode_window),
               ("bench_longseq", bench_longseq),
               ("bench_capsule", bench_capsule),
               ("bench_serving_tp", bench_serving_tp),
               ("bench_serving_moe", bench_serving_moe),
               ("bench_serving_spec", bench_serving_spec)]
        failed = 0
        for fname, fn in fns:
            try:
                print(json.dumps(fn()), flush=True)
            except Exception as e:
                failed += 1
                print(json.dumps({"metric": f"{fname}_ERROR",
                                  "error": str(e)[:300]}), flush=True)
        return 1 if failed else 0
    bench_headline()


if __name__ == "__main__":
    sys.exit(main())
