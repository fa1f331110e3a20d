"""A training job: seeded token batches fed step by step.

A pool of ``pool`` batches is made on the host from the seed and cycled;
each step's batch is put on the device inside a ``pb.train.feed`` span
and the step ends in ``device_get`` of its loss (``pb.train.step``).
The window opens at the first step after warm-up and closes at the end of
the first step that ends after ``--seconds``, so it holds whole steps
only and the rate is taken over all of them and all of that time.
"""
from __future__ import annotations

import math
import time

from perfbench import stats


def prepare(system, traffic, seed, seconds, rehearse):
    import numpy as np
    rng = np.random.default_rng(int(seed))
    pool = []
    for _ in range(traffic["pool"]):
        ids = rng.integers(0, system.vocab, size=(system.batch, system.seq),
                           dtype=np.int32)
        labels = np.concatenate(
            [ids[:, 1:], np.full((system.batch, 1), -100, np.int32)], axis=1)
        pool.append({"input_ids": ids, "labels": labels})
    return {"pool": pool}


def run(system, plan, rec, seconds, session, log):
    import jax
    import numpy as np
    pool = plan["pool"]
    tokens_per_step = system.batch * system.seq
    losses = []
    t0 = time.perf_counter()
    if rec.trace:
        session.schedule(t0 + max(seconds - session.seconds - 2.0, 0.0))
    t_end = t0
    while t_end - t0 < seconds:
        with rec.span("pb.train.feed"):
            batch = jax.device_put(pool[len(losses) % len(pool)])
        with rec.span("pb.train.step"):
            loss = float(np.asarray(jax.device_get(system.step(batch))))
        losses.append(loss)
        t_end = time.perf_counter()
    rec.window = (t0, t_end)
    elapsed = t_end - t0
    vals = {"train_tokens_per_s_chip":
            tokens_per_step * len(losses) / elapsed / system.chips}
    rec.values.update(vals)
    rec.context["tokens_per_s"] = tokens_per_step * len(losses) / elapsed
    finite = all(math.isfinite(x) for x in losses)
    k = min(5, len(losses) // 2)
    falling = k > 0 and \
        sum(losses[-k:]) / k < sum(losses[:k]) / k
    steps = [b - a for a, b in rec.spans["pb.train.step"][-len(losses):]]
    info = {"steps": len(losses), "window_s": elapsed,
            "tokens_per_step": tokens_per_step,
            "step_s_median": stats.median(steps), "step_s_max": max(steps),
            "losses_first5": losses[:5], "losses_last5": losses[-5:],
            "losses_finite": finite, "loss_falling": falling,
            "step_programs": system.step.step_compiles()}
    return {"attempted": len(losses),
            "failed": sum(1 for x in losses if not math.isfinite(x)),
            "ok": finite and falling, "values": vals, "info": info}
