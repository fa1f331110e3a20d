"""The Qwen3-Next hybrid through the normal serving path:
``LLMEngine`` -> ``Scheduler(chunked_prefill=True)`` ->
``serving/server.py``'s HTTP front end — ``serve_engine.ServeSystem``
with this family's model construction, its own plain reference
(``perfbench/reference_qwen3_next.py``) and its own judge.
"""
from __future__ import annotations

import time

from perfbench import reference_qwen3_next as reference
from perfbench.builders import models, serve_engine


def build(cfg, traffic, seed, rec, rehearse, log):
    return HybridServeSystem(cfg, seed, rec, rehearse, log)


def model_config(sz: dict, max_positions: int):
    """The program's config object from the file's published key names:
    ``num_experts`` in the file is the number HELD here, the router keeps
    the published width."""
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig
    return Qwen3NextConfig(
        vocab_size=sz["vocab_size"], hidden_size=sz["hidden_size"],
        num_hidden_layers=sz["num_hidden_layers"],
        full_attention_interval=sz["full_attention_interval"],
        num_attention_heads=sz["num_attention_heads"],
        num_key_value_heads=sz["num_key_value_heads"],
        head_dim=sz["head_dim"],
        partial_rotary_factor=sz["partial_rotary_factor"],
        linear_num_key_heads=sz["linear_num_key_heads"],
        linear_num_value_heads=sz["linear_num_value_heads"],
        linear_key_head_dim=sz["linear_key_head_dim"],
        linear_value_head_dim=sz["linear_value_head_dim"],
        linear_conv_kernel_dim=sz["linear_conv_kernel_dim"],
        moe_intermediate_size=sz["moe_intermediate_size"],
        shared_expert_intermediate_size=(
            sz["shared_expert_intermediate_size"]),
        num_experts=reference.router_width(sz),
        num_experts_per_tok=sz["num_experts_per_tok"],
        norm_topk_prob=sz["norm_topk_prob"],
        experts_held=tuple(sz["experts_held"]),
        max_position_embeddings=max_positions,
        rms_norm_eps=sz["rms_norm_eps"], rope_theta=sz["rope_theta"],
        initializer_range=sz["initializer_range"],
        tie_word_embeddings=sz["tie_word_embeddings"])


def make_model(sz: dict, seed: int, max_positions: int):
    """The model with bf16 (amp O2) weights made on the device from the
    seed in ONE jitted call (``builders/models.py``'s way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import _rope_cos_sin
    from paddle_tpu.models.qwen3_next import Qwen3NextForCausalLM
    from paddle_tpu.ops import random as prandom

    mcfg = model_config(sz, max_positions)
    held = mcfg.held
    assert held[1] == sz["num_experts"], (
        f"experts_held {sz['experts_held']} is not the file's "
        f"num_experts {sz['num_experts']}")
    built = []

    def construct(key):
        with prandom.rng_guard(key):
            model = paddle.amp.decorate(Qwen3NextForCausalLM(mcfg),
                                        level="O2", dtype=sz["dtype"])
        built.append(model)
        return model.raw_state_dict()

    params = jax.jit(construct)(models.seed_key(seed))
    model = built[-1]
    # the object traced above holds tracers; give it the real arrays,
    # and the rope tables again in float32 (they depend on no seed; the
    # O2 cast had made them bf16)
    model.load_raw_state_dict(params)
    rope = _rope_cos_sin(mcfg.max_position_embeddings, mcfg.rotary_dim,
                         mcfg.rope_theta)
    model.rope_cos._value = jnp.asarray(np.cos(rope))
    model.rope_sin._value = jnp.asarray(np.sin(rope))
    jax.block_until_ready(params)
    return model


def through_the_step_recurrence(fn, ops, page: int, budget: int,
                                prompt_len: int, slots: int = 3):
    """One sequence's operands through ``fn`` — the recurrence the step
    programs call, ``(q, k, v, g, beta, state, q_start, q_len, kv_len,
    slot, page_size=)`` — the way the engine hands a request over: the
    first ``prompt_len`` tokens in steps of ``budget`` rows, a step's
    rows as one descriptor a page (several descriptors of one slot in
    one launch, each reading the state the one before wrote), then one
    single-row descriptor a step; the state lives in a pool and starts
    from ``kv_len == 0``.  Returns (outputs [S, Hv, dv], final state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = ops[0].shape[0]
    n_desc = budget // page + 1
    slot = slots - 1
    state = jnp.ones((slots + 1,) + ops[0].shape[1:] + ops[2].shape[-1:],
                     jnp.float32)            # not zeros: kv_len 0 resets
    call = jax.jit(fn, static_argnames="page_size", donate_argnums=(5,))
    outs, pos = [], 0
    while pos < n:
        rows = min(budget, prompt_len - pos) if pos < prompt_len else 1
        q_start = np.zeros(n_desc, np.int32)
        q_len = np.zeros(n_desc, np.int32)
        kv_len = np.zeros(n_desc, np.int32)
        slots_of = np.full(n_desc, slots, np.int32)      # the pad slot
        for d, r0 in enumerate(range(0, rows, page)):
            q_start[d], q_len[d] = r0, min(page, rows - r0)
            kv_len[d], slots_of[d] = pos + r0, slot
        step = [np.zeros((budget,) + x.shape[1:], x.dtype) for x in ops]
        for buf, x in zip(step, ops):
            buf[:rows] = x[pos:pos + rows]
        o, state = call(*(jnp.asarray(b) for b in step), state,
                        *(jnp.asarray(x) for x in (q_start, q_len, kv_len,
                                                   slots_of)),
                        page_size=page)
        outs.append(np.asarray(o[:rows]))
        pos += rows
    return np.concatenate(outs, 0), np.asarray(state[slot])


class HybridServeSystem(serve_engine.ServeSystem):
    def __init__(self, cfg, seed, rec, rehearse, log):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.inference.engine import LLMEngine
        from paddle_tpu.serving.scheduler import Scheduler
        from paddle_tpu.serving.server import start_http_frontend

        self.sz = sz = cfg
        self.rec, self.log, self.seed = rec, log, seed
        eng = dict(sz["engine"])
        t0 = time.perf_counter()
        self.model = make_model(sz, seed, eng["max_len"])
        t1 = time.perf_counter()
        self.engine = LLMEngine(self.model, dtype=getattr(jnp, sz["dtype"]),
                                **eng)
        self.sched = Scheduler(self.engine, **sz["scheduler"])
        self._wrap()
        self.fe = start_http_frontend(self.sched, request_timeout=900.0)
        self.url = self.fe.url
        self.vocab = sz["vocab_size"]
        self.max_len = eng["max_len"]
        jax.block_until_ready((self.engine.cache.k_pages,
                               self.engine.cache.rec_state))
        self.timing = {"weights_s": t1 - t0,
                       "engine_build_s": time.perf_counter() - t1}
        log(f"serve_qwen3_next: {sz['name']} weights {t1 - t0:.1f}s, "
            f"engine up in {time.perf_counter() - t1:.1f}s at {self.url}, "
            f"engine {eng}, scheduler {sz['scheduler']}, state "
            f"{self.engine.cache.state_bytes()} B")

    def check(self) -> dict:
        """Seeded probes through the normal path (long enough that the
        recurrent state crosses page chunks, steps with several
        descriptors of one request, and decode windows), then the plain
        reference teacher-forced over prompt + served tokens."""
        import numpy as np
        t0 = time.perf_counter()
        p = self.sz["probe"]
        rng = np.random.default_rng(self.seed + 1)
        params = reference.canonical(self.model.raw_state_dict(), self.sz)
        rows = []
        for i in range(p["prompts"]):
            prompt = rng.integers(0, self.vocab,
                                  size=p["prompt_len"]).tolist()
            served = self.stream(f"pb-probe-{i}", prompt, p["new_tokens"])
            ref = reference.logits(params, self.sz, prompt + served[:-1])
            rows.append(reference.judge_served(ref, len(prompt), served))
        # the float32 recurrent state, where it can be seen (the
        # reference's section on it says why not in the tokens): the
        # state the served probes left in the pools, and the step
        # programs' recurrence against the plain one
        bits = reference.judge_state_bits(self.engine.cache.rec_state)
        rec = self.check_recurrence()
        self.timing["probe_s"] = time.perf_counter() - t0
        ok = all(r["ok"] for r in rows) and bits["ok"] and rec["ok"]
        self.log(f"serve_qwen3_next: probes ok={ok} {rows} state {bits} "
                 f"recurrence {rec} in {self.timing['probe_s']:.1f}s")
        return {"ok": ok, "probes": rows, "state": bits,
                "recurrence": rec}

    def check_recurrence(self, fn=None) -> dict:
        """The recurrence the step programs call (``fn``: a control's
        stand-in) over one seeded sequence as long as a probe, handed
        over as the engine hands a request over, against the plain
        token-by-token recurrence."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.gated_delta import ragged_gated_delta
        p, eng = self.sz["probe"], self.sz["engine"]
        ops = reference.recurrence_inputs(
            self.sz, self.seed + 2, p["prompt_len"] + p["new_tokens"])
        o_ref, s_ref = reference.recurrence(*map(jnp.asarray, ops))
        o, s = through_the_step_recurrence(
            fn or ragged_gated_delta, ops, eng["page_size"],
            eng["prefill_token_budget"], p["prompt_len"])
        return reference.judge_recurrence(o_ref, s_ref, o, s)
