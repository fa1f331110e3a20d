"""Models at a configuration file's sizes, with weights made on the
device from the seed in ONE jitted call.

The program's constructors draw every leaf from its own initialiser.
Run eagerly that is a dispatch (and a compile) per leaf; traced under
``jax.jit`` with the program's ``rng_guard`` supplying a traced key, the
whole construction becomes one program whose outputs are the weights, in
the type they are used in.  The key is an argument, so every seed shares
one compiled program and the persistent cache serves all later runs.
"""
from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def model_config(sz: dict, max_positions: int):
    """The program's config object from the file's published key names."""
    arch = sz["arch"]
    if arch == "qwen2_moe":
        from paddle_tpu.models.qwen2_moe import Qwen2MoeConfig
        return Qwen2MoeConfig(
            vocab_size=sz["vocab_size"], hidden_size=sz["hidden_size"],
            num_hidden_layers=sz["num_hidden_layers"],
            num_attention_heads=sz["num_attention_heads"],
            num_key_value_heads=sz["num_key_value_heads"],
            moe_intermediate_size=sz["moe_intermediate_size"],
            shared_expert_intermediate_size=(
                sz["n_shared_experts"] * sz["moe_intermediate_size"]),
            num_experts=sz["n_routed_experts"],
            num_experts_per_tok=sz["num_experts_per_tok"],
            max_position_embeddings=max_positions,
            rms_norm_eps=sz["rms_norm_eps"], rope_theta=sz["rope_theta"],
            initializer_range=sz["initializer_range"],
            norm_topk_prob=sz["norm_topk_prob"], attention_bias=False,
            use_shared_expert_gate=False,
            tie_word_embeddings=sz["tie_word_embeddings"])
    if arch == "llama":
        from paddle_tpu.models.llama import LlamaConfig
        return LlamaConfig(
            vocab_size=sz["vocab_size"], hidden_size=sz["hidden_size"],
            intermediate_size=sz["intermediate_size"],
            num_hidden_layers=sz["num_hidden_layers"],
            num_attention_heads=sz["num_attention_heads"],
            num_key_value_heads=sz["num_key_value_heads"],
            max_position_embeddings=max_positions,
            rms_norm_eps=sz["rms_norm_eps"], rope_theta=sz["rope_theta"],
            initializer_range=sz["initializer_range"],
            tie_word_embeddings=sz["tie_word_embeddings"],
            attention_bias=sz["bias"], **sz.get("train_model", {}))
    raise ValueError(f"no model for architecture {arch!r}")


def make_model(sz: dict, seed: int, max_positions: int):
    """The model, with bf16 (amp O2) weights on the device."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.ops import random as prandom

    mcfg = model_config(sz, max_positions)
    if sz["arch"] == "qwen2_moe":
        from paddle_tpu.models.qwen2_moe import Qwen2MoeForCausalLM as cls
    else:
        from paddle_tpu.models.llama import LlamaForCausalLM as cls
    built = []

    def construct(key):
        with prandom.rng_guard(key):
            model = paddle.amp.decorate(cls(mcfg), level="O2",
                                        dtype=sz["dtype"])
        built.append(model)
        return (model.raw_state_dict(),
                {k: b._value for k, b in model.named_buffers()})

    params, buffers = jax.jit(construct)(seed_key(seed))
    model = built[-1]
    # the object traced above holds tracers; give it the real arrays
    model.load_raw_state_dict(params)
    for k, b in model.named_buffers():
        b._value = buffers[k]
    jax.block_until_ready(params)
    return model
