"""The Nemotron-H hybrid through the normal serving path:
``LLMEngine`` -> ``Scheduler(chunked_prefill=True)`` ->
``serving/server.py``'s HTTP front end — ``serve_engine.ServeSystem``
with this family's model construction, its own plain reference
(``perfbench/reference_nemotron_h.py``) and its own judges.
"""
from __future__ import annotations

import time

from perfbench import reference_nemotron_h as reference
from perfbench.builders import models, serve_engine


def build(cfg, traffic, seed, rec, rehearse, log):
    return NemotronHServeSystem(cfg, seed, rec, rehearse, log)


def model_config(sz: dict, max_positions: int):
    """The program's config object from the file's published key names:
    ``n_routed_experts`` in the file is the number HELD here, the router
    keeps the published width."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "chunk_size", "time_step_min", "time_step_max",
            "time_step_floor", "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "num_experts_per_tok",
            "norm_topk_prob", "layer_norm_epsilon", "initializer_range",
            "tie_word_embeddings")
    return NemotronHConfig(
        n_routed_experts=reference.router_width(sz),
        routed_scaling_factor=float(sz["routed_scaling_factor"]),
        experts_held=tuple(sz["experts_held"]),
        max_position_embeddings=max_positions,
        **{k: sz[k] for k in keys})


def make_model(sz: dict, seed: int, max_positions: int):
    """The model with bf16 (amp O2) weights made on the device from the
    seed in ONE jitted call (``builders/models.py``'s way)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
    from paddle_tpu.ops import random as prandom

    mcfg = model_config(sz, max_positions)
    assert mcfg.held[1] == sz["n_routed_experts"], (
        f"experts_held {sz['experts_held']} is not the file's "
        f"n_routed_experts {sz['n_routed_experts']}")
    built = []

    def construct(key):
        with prandom.rng_guard(key):
            model = paddle.amp.decorate(NemotronHForCausalLM(mcfg),
                                        level="O2", dtype=sz["dtype"])
        built.append(model)
        return model.raw_state_dict()

    params = jax.jit(construct)(models.seed_key(seed))
    model = built[-1]
    # the object traced above holds tracers; give it the real arrays,
    # and the width-0 rotary tables again (they depend on no seed)
    model.load_raw_state_dict(params)
    empty = jnp.zeros((mcfg.max_position_embeddings, 0), jnp.float32)
    model.rope_cos._value = model.rope_sin._value = empty
    jax.block_until_ready(params)
    return model


def through_the_step_recurrence(fn, ops, state_shape, page: int,
                                budget: int, prompt_len: int,
                                slots: int = 3):
    """One sequence's per-token operands ``ops`` through ``fn`` — the
    recurrence the step programs call, as ``fn(*ops_of_the_step, state,
    q_start, q_len, kv_len, slot, page_size=)`` — the way the engine
    hands a request over: the first ``prompt_len`` tokens in steps of
    ``budget`` rows, a step's rows as one descriptor a page (several
    descriptors of one slot in one launch, each reading the state the
    one before wrote), then one single-row descriptor a step; the state
    lives in a pool of ``slots + 1`` and starts from ``kv_len == 0``.
    Returns (outputs [S, ..], the slot's final state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = ops[0].shape[0]
    n_desc = budget // page + 1
    slot = slots - 1
    state = jnp.ones((slots + 1,) + tuple(state_shape),
                     jnp.float32)            # not zeros: kv_len 0 resets
    call = jax.jit(fn, static_argnames="page_size",
                   donate_argnums=(len(ops),))
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


class NemotronHServeSystem(serve_engine.ServeSystem):
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
        log(f"serve_nemotron_h: {sz['name']} weights {t1 - t0:.1f}s, "
            f"engine up in {time.perf_counter() - t1:.1f}s at {self.url}, "
            f"engine {eng}, scheduler {sz['scheduler']}, state "
            f"{self.engine.cache.state_bytes()} B")

    def check(self) -> dict:
        """Seeded probes through the normal path (long enough that the
        recurrent state crosses page chunks, steps with several
        descriptors of one request, and decode windows), then the plain
        reference teacher-forced over prompt + served tokens; the
        recurrence, the pools' bits and the expert layer each against
        the reference where they can be seen."""
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
        bits = reference.judge_state_bits(self.engine.cache.rec_state)
        rec = self.check_recurrence()
        exp = self.check_expert_layer()
        self.timing["probe_s"] = time.perf_counter() - t0
        ok = all(r["ok"] for r in rows) and bits["ok"] and rec["ok"] \
            and exp["ok"]
        self.log(f"serve_nemotron_h: probes ok={ok} {rows} state {bits} "
                 f"recurrence {rec} expert layer {exp} in "
                 f"{self.timing['probe_s']:.1f}s")
        return {"ok": ok, "probes": rows, "state": bits,
                "recurrence": rec, "expert_layer": exp}

    def check_recurrence(self, fn=None) -> dict:
        """The recurrence the step programs call (``fn``: a control's
        stand-in) over one seeded sequence as long as a probe, handed
        over as the engine hands a request over, against the plain
        token-by-token recurrence."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.mamba2_ssd import ragged_ssd
        p, eng = self.sz["probe"], self.sz["engine"]
        x, dt, a, b, c, d = reference.recurrence_inputs(
            self.sz, self.seed + 2, p["prompt_len"] + p["new_tokens"])
        y_ref, s_ref = reference.recurrence(
            *map(jnp.asarray, (x, dt, a, b, c, d)))
        step = fn or ragged_ssd

        def call(x_, dt_, b_, c_, state, *desc, page_size):
            return step(x_, dt_, jnp.asarray(a), b_, c_, jnp.asarray(d),
                        state, *desc, page_size=page_size)
        y, s = through_the_step_recurrence(
            call, (x, dt, b, c), (x.shape[1], x.shape[2], b.shape[2]),
            eng["page_size"], eng["prefill_token_budget"],
            p["prompt_len"])
        return reference.judge_recurrence(y_ref, s_ref, y, s)

    def check_expert_layer(self, **route_kw) -> dict:
        """The expert layer the step programs call, on seeded rows with
        the first expert block's weights, against the reference's (one
        of ``route``'s control readings with ``route_kw``)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.inference.moe_dispatch import moe_ffn
        # the first expert block's weights: the engine's own dict
        lay = next(lp for lp in self.engine._stack if "router" in lp)
        arch = self.engine._arch
        n = self.sz["probe"]["expert_rows"]
        h = jnp.asarray(reference.expert_rows(self.sz, self.seed + 3, n),
                        getattr(jnp, self.sz["dtype"]))
        live = jnp.ones(n, bool)
        run = jax.jit(lambda h, lay, arch: moe_ffn(h, lay, arch, live)[0],
                      static_argnums=2)
        whole = run(h, lay, arch)
        routed = run(h, lay, arch._replace(shared=False))
        hf = h.astype(jnp.float32)
        return reference.judge_expert_layer(
            reference.moe_in_blocks(hf, lay, self.sz, shared=False,
                                    **route_kw),
            reference.moe_in_blocks(hf, lay, self.sz, **route_kw),
            routed, whole)
