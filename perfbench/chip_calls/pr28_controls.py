"""PR 28: the lower-precision readings put through the comparison that
decides ``correct`` ON THE CHIP, and the served logits' distance from the
reference (why no limit on served tokens or logits can hold the
recurrent state to float32; PERF.md §4).

    chiprun --chips 1 --timeout 1500 -- python3 perfbench/chip_calls/pr28_controls.py controls [seed]
    chiprun --chips 1 --timeout 1500 -- python3 perfbench/chip_calls/pr28_controls.py logits [seed]
    JAX_PLATFORMS=cpu python3 perfbench/chip_calls/pr28_controls.py controls 7 rehearse   # a dry run, tiny sizes

``controls`` builds the cell's system as ``perfbench.run`` does, runs its
``check()`` (every limit has to pass) and then each control through the
SAME judges: the plain recurrence with its state rounded to bf16 after
every token, the step programs' recurrence with its pool rounded to bf16
between steps and with the chip's default matmul precision (operands of
float32 products rounded to bf16), a bf16 copy of the pools the probes
left, and the reference with the decay ``exp(g)`` dropped teacher-forced
over the served tokens.  Every control has to read NOT ok.

``logits`` serves the probes through a step program that hands the
logits it samples from to the host (a debug callback: another program
than the one measured, for this reading only) and prints their relative
L2 distance from the float32 reference, beside the same distance for the
reference with a bf16 state.
"""
import faulthandler
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))            # the checkout's root

WATCHDOG_S = 1200
CELL = "hybrid_serve_longctx"
REHEARSE = False              # the tiny sizes, on any platform (a dry run)


def build(seed):
    import jax
    from paddle_tpu.runtime.compile_cache import enable_compile_cache
    from perfbench import manifest
    from perfbench.record import Record
    from perfbench.run import make_log
    import time
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
    from paddle_tpu.ops.pallas.gated_delta import ragged_gated_delta
    from perfbench import reference_qwen3_next as reference
    system, cfg = build(seed)
    out = {"sound": system.check()}
    bf16 = jnp.bfloat16

    def bf16_pool(*a, **kw):
        o, state = ragged_gated_delta(*a, **kw)
        return o, reference.round_to(state, bf16)

    def default_precision(*a, **kw):
        with jax.default_matmul_precision("default"):
            return ragged_gated_delta.__wrapped__(*a, **kw)
    p = cfg["probe"]
    ops = reference.recurrence_inputs(cfg, seed + 2,
                                      p["prompt_len"] + p["new_tokens"])
    dev = [jnp.asarray(x) for x in ops]
    o_ref, s_ref = reference.recurrence(*dev)
    ctl = {
        "reference_bf16_state": reference.judge_recurrence(
            o_ref, s_ref, *reference.recurrence(*dev, state_dtype=bf16)),
        "program_bf16_pool": system.check_recurrence(fn=bf16_pool),
        "program_default_matmul_precision":
            system.check_recurrence(fn=default_precision),
        "pools_in_bf16": reference.judge_state_bits(tuple(
            a.astype(bf16) for a in system.engine.cache.rec_state)),
    }
    # a dropped decay, by the token judge: A_log = -inf makes g = 0
    sd = dict(system.model.raw_state_dict())
    for name in list(sd):
        if name.endswith("linear_attn.A_log"):
            sd[name] = jnp.full_like(sd[name], -jnp.inf)
    params = reference.canonical(sd, cfg)
    rng = np.random.default_rng(seed + 1)
    prompt = rng.integers(0, cfg["vocab_size"],
                          size=p["prompt_len"]).tolist()
    served = system.stream("pb-control-0", prompt, p["new_tokens"])
    ctl["reference_dropped_decay"] = reference.judge_served(
        reference.logits(params, cfg, prompt + served[:-1]), len(prompt),
        served)
    out["controls"] = ctl
    out["ok"] = bool(out["sound"]["ok"]) and not any(
        c["ok"] for c in ctl.values())
    system.close()
    return out


def served_logits(seed):
    """Relative L2 of the logits the step programs sample from against
    the float32 reference, over the probes' judged positions."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import sampling
    from perfbench import reference_qwen3_next as reference
    seen, last_row = [], [None]
    real = sampling.sample_logits

    def keep(logits):
        if last_row[0] is not None:
            seen.append((np.asarray(logits[last_row[0]], np.float32),
                         np.asarray(logits[0], np.float32)))

    def spy(logits, *a, **kw):
        jax.debug.callback(keep, logits, ordered=True)
        return real(logits, *a, **kw)
    sampling.sample_logits = spy
    system, cfg = build(seed)
    p, budget = cfg["probe"], cfg["engine"]["prefill_token_budget"]
    params = reference.canonical(system.model.raw_state_dict(), cfg)
    rng = np.random.default_rng(seed + 1)
    out = {"probes": []}
    for i in range(p["prompts"]):
        prompt = rng.integers(0, cfg["vocab_size"],
                              size=p["prompt_len"]).tolist()
        # the probe is alone, so its rows start at 0: the first token is
        # sampled from the last prompt row of the step that ends the
        # prompt, every later one from row 0 of a decode step
        before = (len(prompt) - 1) // budget
        del seen[:]
        last_row[0] = (len(prompt) - 1) % budget
        served = system.stream(f"pb-logits-{i}", prompt, p["new_tokens"])
        jax.effects_barrier()
        last_row[0] = None
        got = np.stack([seen[before][0]] + [
            pair[1] for pair in seen[before + 1:before + len(served)]])
        ids = prompt + served[:-1]
        ref = reference.logits(params, cfg, ids)[len(prompt) - 1:]
        low = reference.logits(params, cfg, ids,
                               state_dtype=jnp.bfloat16)[len(prompt) - 1:]
        out["probes"].append({
            "positions": int(got.shape[0]), "callbacks": len(seen),
            "rows_are_the_sampled_ones": bool(
                (got.argmax(-1) == np.asarray(served)).all()),
            "rel_l2_program": reference.rel_l2(got, ref),
            "rel_l2_reference_bf16_state": reference.rel_l2(low, ref),
            "argmax_equal_reference": int(
                (got.argmax(-1) == ref.argmax(-1)).sum())})
    system.close()
    out["ok"] = all(r["rows_are_the_sampled_ones"] for r in out["probes"])
    return out


def main(which="controls", seed="2147484001", rehearse=""):
    import jax
    global REHEARSE
    REHEARSE = rehearse == "rehearse"
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    if jax.devices()[0].platform != "tpu" and not REHEARSE:
        print("pr28_controls: no TPU", file=sys.stderr)
        return 2
    res = {"controls": controls, "logits": served_logits}[which](int(seed))
    print(json.dumps(res, default=str))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.stdout.flush()
    code = main(*sys.argv[1:4])
    sys.stdout.flush()
    os._exit(code)
