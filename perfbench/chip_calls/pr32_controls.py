"""PR 32: the seven controls put through the comparison that decides
``correct`` ON THE CHIP.  Each has to read NOT ok through the cell's own
judges (``perfbench/reference_nemotron_h.py``), beside a sound
``check()`` that passes every one of them.

    chiprun --chips 1 --timeout 1500 -- python3 perfbench/chip_calls/pr32_controls.py [seed]
    JAX_PLATFORMS=cpu python3 perfbench/chip_calls/pr32_controls.py 7 rehearse   # a dry run, tiny sizes

It builds the cell's system as ``perfbench.run`` does, runs its
``check()``, then:

- *a bf16 state*: the plain recurrence with its state rounded to bf16
  after every token, and the step programs' recurrence with its pool
  rounded to bf16 between steps (``judge_recurrence``); a bf16 copy of
  the pools the probes left (``judge_state_bits``);
- *the chip's default matmul precision in the recurrence*: the step
  programs' recurrence with the operands of its float32 products
  rounded to bf16 (``judge_recurrence``);
- *the decay dropped* and *D dropped*: the step programs' recurrence
  with ``a = 0`` and with ``d = 0`` (``judge_recurrence``), and the
  reference with ``A_log = -inf`` / ``D = 0`` teacher-forced over
  served tokens (``judge_served``, reported; the recurrence judge is
  the one that has to fail);
- *the correction bias dropped from the selection*, *the route scale
  dropped*, *softmax in place of sigmoid*: the reference's expert layer
  with that router against the program's (``judge_expert_layer``), and
  the reference with that router teacher-forced over served tokens
  (``judge_served``, reported).
"""
import faulthandler
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))            # the checkout's root

WATCHDOG_S = 1300
CELL = "ssm_moe_serve_reason"
REHEARSE = False              # the tiny sizes, on any platform (a dry run)
ROUTERS = {"correction_bias_dropped": dict(use_bias=False),
           "route_scale_dropped": dict(scale=1.0),
           "softmax_router": dict(scoring="softmax")}


def build(seed):
    import time
    import jax
    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    from perfbench import manifest
    from perfbench.record import Record
    from perfbench.run import make_log
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    bench = manifest.benchmark()
    cell = manifest.cell(bench, CELL)
    cfg = manifest.at_size(manifest.config(bench, cell["config"]),
                           REHEARSE)
    traffic = manifest.at_size(manifest.traffic(cell["traffic"]),
                               REHEARSE)
    log = make_log(time.perf_counter())
    builder = manifest.module("builders", cfg["builder"])
    system = builder.build(cfg, traffic, seed, Record(False), REHEARSE,
                           log)
    system.warm(None)
    return system, cfg


def controls(seed):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.mamba2_ssd import ragged_ssd
    from perfbench import reference_nemotron_h as reference
    system, cfg = build(seed)
    out = {"sound": system.check()}
    bf16 = jnp.bfloat16

    def bf16_pool(*a, **kw):
        y, state = ragged_ssd(*a, **kw)
        return y, reference.round_to(state, bf16)

    def default_precision(*a, **kw):
        with jax.default_matmul_precision("default"):
            return ragged_ssd.__wrapped__(*a, **kw)

    def no_decay(x, dt, a, *rest, **kw):
        return ragged_ssd(x, dt, a * 0.0, *rest, **kw)

    def no_skip(x, dt, a, b, c, d, *rest, **kw):
        return ragged_ssd(x, dt, a, b, c, d * 0.0, *rest, **kw)
    p = cfg["probe"]
    ops = reference.recurrence_inputs(cfg, seed + 2,
                                      p["prompt_len"] + p["new_tokens"])
    dev = [jnp.asarray(v) for v in ops]
    y_ref, s_ref = reference.recurrence(*dev)
    ctl = {
        "reference_bf16_state": reference.judge_recurrence(
            y_ref, s_ref, *reference.recurrence(*dev, state_dtype=bf16)),
        "program_bf16_pool": system.check_recurrence(fn=bf16_pool),
        "pools_in_bf16": reference.judge_state_bits(tuple(
            a.astype(bf16) for a in system.engine.cache.rec_state)),
        "program_default_matmul_precision":
            system.check_recurrence(fn=default_precision),
        "program_decay_dropped": system.check_recurrence(fn=no_decay),
        "program_D_dropped": system.check_recurrence(fn=no_skip),
    }
    for name, kw in ROUTERS.items():
        ctl["expert_layer_" + name] = system.check_expert_layer(**kw)
    # what the token judge says of the same controls (reported: at these
    # widths the held share's routed part is a tenth of a block's output)
    sd = dict(system.model.raw_state_dict())
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg["vocab_size"],
                          size=p["prompt_len"]).tolist()
    served = system.stream("pb-control-0", prompt, p["new_tokens"])
    ids = prompt + served[:-1]
    tokens = {}

    def without(suffix, value):
        return reference.canonical(
            {k: (jnp.full_like(v, value) if k.endswith(suffix) else v)
             for k, v in sd.items()}, cfg)
    sound = reference.canonical(sd, cfg)
    for name, params, kw in (
            [("decay_dropped", without("mixer.A_log", -jnp.inf), {}),
             ("D_dropped", without("mixer.D", 0.0), {})]
            + [(n, sound, kw) for n, kw in ROUTERS.items()]):
        tokens[name] = reference.judge_served(
            reference.logits(params, cfg, ids, **kw), len(prompt), served)
    tokens["sound"] = reference.judge_served(
        reference.logits(sound, cfg, ids), len(prompt), served)
    out["controls"] = ctl
    out["token_judge_on_the_controls"] = {
        k: {"ok": v["ok"], "equal": v["equal"], "positions":
            v["positions"], "max_gap_ulps": v["max_gap_ulps"]}
        for k, v in tokens.items()}
    out["ok"] = bool(out["sound"]["ok"]) and not any(
        c["ok"] for c in ctl.values())
    system.close()
    return out


def main(seed="2147489001", rehearse=""):
    import jax
    global REHEARSE
    REHEARSE = rehearse == "rehearse"
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    if jax.devices()[0].platform != "tpu" and not REHEARSE:
        print("pr32_controls: no TPU", file=sys.stderr)
        return 2
    res = controls(int(seed))
    print(json.dumps(res, default=str))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.stdout.flush()
    code = main(*sys.argv[1:3])
    sys.stdout.flush()
    os._exit(code)
