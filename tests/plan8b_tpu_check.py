"""Real-TPU compiled activation check for the 8B plan (subprocess).

Compiles (AOT — nothing executes, state stays on the host CPU backend)
the TRUE-width Llama-3-8B train step at num_layers=1 and 2 with the
REAL Mosaic flash kernel and per-chip micro-batch 1 x seq 8192, then
reads XLA's own ``compiled.memory_analysis()`` temp bytes.  The
per-layer delta x32 (+ the layer-independent base: CE chunk workspace,
flash workspace, embed/head temps) is the compiler's answer to the
question plan8b_worker.py answers analytically.  Prints ONE json line.

Needs a TPU (``JAX_PLATFORMS=tpu``, and no other process on the chip);
exits 86 (skip) when no TPU backend is available.
"""
import json
import os
import sys

# repo-root import without PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

try:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"skip": f"platform {dev.platform}"}))
        sys.exit(86)
except Exception as e:  # noqa: BLE001
    print(json.dumps({"skip": str(e)[:200]}))
    sys.exit(86)

import paddle_tpu as paddle  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from plan8b_model import FFN, HIDDEN, SEQ, VOCAB  # noqa: E402
from plan8b_model import zero_init_params  # noqa: E402

zero_init_params()
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa

CPU = jax.local_devices(backend="cpu")[0]


def temp_bytes(layers):
    """Temp (activation+workspace) bytes of the compiled fwd+bwd step.

    Uses bf16 params + plain SGD so the STATE stays under the v5e's
    compile-time HBM check (the O2 master/moment state of even the
    2-layer true-width model exceeds 16 GB); the TEMP allocation —
    the quantity the analytic activation model predicts — is set by
    the bf16 forward/backward exactly as in the O2 recipe."""
    from paddle_tpu.jit.train import CompiledTrainStep, _to_arrays

    cfg = LlamaConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=FFN,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=SEQ,
        rope_theta=500000.0, tie_word_embeddings=False,
        recompute=True, recompute_granularity="core_attn")
    with jax.default_device(CPU):
        model = LlamaForCausalLM(cfg)
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
        opt = paddle.optimizer.SGD(learning_rate=1e-4,
                                   parameters=model.parameters())

        def loss_fn(m, b):
            return m(b["input_ids"], labels=b["labels"])

        step = CompiledTrainStep(model, loss_fn, opt)
        step._build()
        ids = np.ones((1, SEQ), np.int32)
        batch = _to_arrays({"input_ids": ids, "labels": ids})
        key = jax.random.PRNGKey(0)

    sds = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), t)
    lowered = step._step_fn.lower(sds(step.state), sds(batch),
                                  jax.ShapeDtypeStruct((2,), key.dtype),
                                  np.float32(1e-4))
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    return int(ma.temp_size_in_bytes)


t1 = temp_bytes(1)
t2 = temp_bytes(2)
per_layer = t2 - t1
base = t1 - per_layer
print(json.dumps({
    "temp_1layer_gb": round(t1 / 1e9, 3),
    "temp_2layer_gb": round(t2 / 1e9, 3),
    "per_layer_gb": round(per_layer / 1e9, 4),
    "base_gb": round(base / 1e9, 4),
    "extrapolated_32layer_gb": round((base + 32 * per_layer) / 1e9, 2),
}))
