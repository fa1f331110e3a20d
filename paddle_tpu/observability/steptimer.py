"""StepTimer — training-step wall time / throughput / MFU reporter.

The async-dispatch trap: a jitted train step RETURNS before the device
finishes, so naive `time.perf_counter()` around the call measures
python dispatch, not the step.  `stop(fence=...)` takes the step's
outputs (state pytree and/or loss) and `jax.block_until_ready`s them
before reading the clock, so the recorded interval is the real
device-inclusive step time.  (The fence serializes dispatch with the
device — that is the point: honest numbers.  Attach the timer to every
Nth step if the pipeline bubble matters.)

MFU is estimated as ``flops_per_step / (step_time * peak_flops)`` with
FLOPs taken from the jitted step's XLA ``cost_analysis()``
(`jit.train.CompiledTrainStep.step_flops`) and the chip peak from
`device_peak_flops()`.  Caveats: XLA's cost model counts the HLO it
compiled (rematerialized forwards count twice, fused ops may fold), and
peak table entries are dense-bf16 — treat MFU as a tracking metric, not
a leaderboard number.  Off-TPU there is no meaningful peak, so MFU is
not reported unless ``peak_flops`` is passed explicitly.

Everything flows to BOTH sinks: the metrics registry (Prometheus /
JSONL exposition) and, when given, a visualdl-style writer
(``add_scalar``) so TensorBoard shows the same series.
"""
from __future__ import annotations

import time
from typing import Optional

from .metrics import MetricRegistry, get_registry

__all__ = ["StepTimer", "device_peak_flops"]

# peak dense-bf16 FLOP/s by PJRT device_kind substring (the package
# stays importable standalone; the benchmark keeps its own table of
# peaks, perfbench/manifest.py)
_PEAK_FLOPS = [
    ("v6e", 918e12), ("v6", 918e12), ("v5p", 459e12), ("v5e", 197e12),
    ("v5lite", 197e12), ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
]

_STEP_BUCKETS = (.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1.0,
                 2.5, 5.0, 10.0, 30.0)


def device_peak_flops() -> Optional[float]:
    """Dense-bf16 peak FLOP/s of the local accelerator, or None when
    unknown (CPU hosts: MFU is meaningless there)."""
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        return None
    k = kind.lower().replace(" ", "").replace("tpu", "")
    for sub, peak in _PEAK_FLOPS:
        if sub in k:
            return peak
    return None


class StepTimer:
    """Usage (hapi.Model.fit wires this automatically):

        timer = StepTimer(prefix="train", writer=log_writer)
        timer.flops_per_step = step.step_flops(batch)   # optional, MFU
        for batch in loader:
            timer.tokens_per_step = batch_tokens
            timer.start()
            state = train_step(batch)
            timer.stop(fence=state)     # blocks, then records
    """

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 writer=None, prefix: str = "train",
                 tokens_per_step: Optional[int] = None,
                 flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        reg = registry or get_registry()
        self.prefix = prefix
        self.writer = writer
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self.peak_flops = peak_flops if peak_flops is not None \
            else device_peak_flops()
        self._hist = reg.histogram(
            f"{prefix}_step_seconds",
            "Wall time per training step (block_until_ready fenced).",
            buckets=_STEP_BUCKETS)
        self._steps = reg.counter(f"{prefix}_steps_total",
                                  "Training steps timed.")
        self._tok_rate = reg.gauge(
            f"{prefix}_tokens_per_sec",
            "Token throughput of the last timed step (token count = "
            "elements of the step's first input).")
        self._mfu = reg.gauge(
            f"{prefix}_mfu",
            "Estimated model FLOPs utilization of the last timed step "
            "(XLA cost_analysis FLOPs / chip dense-bf16 peak).")
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()
        return self

    def stop(self, fence=None) -> Optional[float]:
        """Record one step ended now.  ``fence`` is a pytree of jax
        arrays (the step's outputs/state) synced before the clock is
        read; without it the measurement is dispatch-only."""
        if self._t0 is None:
            return None
        if fence is not None:
            import jax
            jax.block_until_ready(fence)
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self._hist.observe(dt)
        self._steps.inc()
        step_i = int(self._steps.value)
        scalars = {f"{self.prefix}/step_time_ms": dt * 1e3}
        if self.tokens_per_step:
            rate = self.tokens_per_step / dt if dt > 0 else 0.0
            self._tok_rate.set(rate)
            scalars[f"{self.prefix}/tokens_per_sec"] = rate
        if self.flops_per_step and self.peak_flops and dt > 0:
            mfu = self.flops_per_step / (dt * self.peak_flops)
            self._mfu.set(mfu)
            scalars[f"{self.prefix}/mfu"] = mfu
        if self.writer is not None:
            for tag, v in scalars.items():
                self.writer.add_scalar(tag, v, step=step_i)
        return dt

    # context-manager sugar: fence must be handed to stop() directly
    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def summary(self) -> dict:
        return {"steps": int(self._steps.value),
                "step_seconds_mean": self._hist.mean,
                "tokens_per_sec": self._tok_rate.value,
                "mfu": self._mfu.value}
