"""Run one cell of BENCHMARK.json once, in this process.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, check against the plain reference, measure for
``--seconds``, and print as the LAST line of standard output one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced).  Everything else (set-up
phases, request counts, generator lateness) goes on earlier lines.  With
``--trace 0`` the metrics are the cell's end-to-end metrics and the
profiler is off; with ``--trace 1`` they are its per-layer metrics.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.  ``BENCH_RUN`` in the environment is ignored.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up counts from here

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
import threading                    # noqa: E402

from perfbench import manifest      # noqa: E402
from perfbench.record import Record  # noqa: E402

TRACE_SECONDS = 4.0
TRACE_DIR = ".perfbench_trace"      # inside the checkout, git-ignored
WINDOW_SPAN = "pb.trace.window"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def make_log(t_start):
    def log(*a):
        print(f"[perfbench {time.perf_counter() - t_start:7.2f}s]", *a,
              flush=True)
    return log


class CompileCounter:
    """Every program JAX lowers in this process, watched or not: a new
    shape inside the window shows here even where the persistent cache
    then spares the compile."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self.n += 1


class TraceSession:
    """The profiler over a short stretch of the window, driven from a
    thread of its own so that neither starting nor stopping it stalls
    the driver.  Host spans named ``pb.*`` land in the same trace; the
    Python tracer is off (it slows the host and bloats the file)."""

    def __init__(self, workload: str, seconds: float = TRACE_SECONDS):
        self.dir = os.path.join(TRACE_DIR, workload)
        self.seconds = seconds
        self._thread = None
        self.cost_s = None

    def schedule(self, t_start: float):
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(target=self._run, args=(t_start,),
                                        name="pb-trace", daemon=True)
        self._thread.start()

    def _run(self, t_start):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        time.sleep(max(t_start - time.perf_counter(), 0.0))
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            time.sleep(self.seconds)
        t2 = time.perf_counter()
        jax.profiler.stop_trace()
        self.cost_s = (t1 - t0) + (time.perf_counter() - t2)

    def finish(self):
        """Wait for the profiler to write its file, then reduce it."""
        from perfbench import trace_reduce
        if self._thread is None:
            return None
        self._thread.join(timeout=300.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        return trace_reduce.summarize_dir(self.dir, WINDOW_SPAN)


def device_report(devices, all_devices) -> dict:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(all_devices), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, traffic_override=None,
             t_start: float = None, out=print) -> int:
    """One run.  ``rehearse`` (the tests only) takes the configuration's
    tiny sizes on whatever platform JAX finds."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = make_log(t_start)
    bench = manifest.benchmark()
    cell = manifest.cell(bench, workload)
    cfg = manifest.at_size(manifest.config(bench, cell["config"]), rehearse)
    traffic = manifest.at_size(manifest.traffic(cell["traffic"]), rehearse)
    for k, v in (traffic_override or {}).items():
        traffic[k] = {**traffic[k], **v} if isinstance(v, dict) else v

    import jax
    all_devices = jax.devices()
    devices = all_devices[:cell["chips"]]
    if not rehearse and (all_devices[0].platform != "tpu"
                         or len(all_devices) < cell["chips"]):
        print(f"perfbench: {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(all_devices)} x "
              f"{all_devices[0].platform!r}.  Refusing to run.",
              file=sys.stderr)
        return 2
    cache = None
    if not rehearse:
        # the program's one place for the cache directory: where
        # JAX_COMPILATION_CACHE_DIR says, else ./.jax_cache in the checkout
        from paddle_tpu.runtime.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        # keep every program, however quick to compile: the second run
        # of a cell then compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    peaks = None if rehearse and all_devices[0].platform != "tpu" \
        else manifest.peaks(devices[0].device_kind)
    log(f"{workload}: seed {seed}, {seconds}s, trace {int(trace)}, "
        f"{len(all_devices)} x {devices[0].device_kind}, jax "
        f"{jax.__version__}, compile cache {cache}")

    from paddle_tpu.observability import introspection
    rec = Record(trace)
    rec.context.update(cfg=cfg, traffic=traffic, chips=cell["chips"],
                       peaks=peaks)
    lowered = CompileCounter()
    watch = introspection.enable_compile_watch(on_recompile="warn",
                                               enable_metrics=False)
    session = TraceSession(workload)
    builder = manifest.module("builders", cfg["builder"])
    driver = manifest.module("drivers", traffic["driver"])
    system = builder.build(cfg, traffic, seed, rec, rehearse, log)
    try:
        plan = driver.prepare(system, traffic, seed, seconds, rehearse)
        system.warm(plan)
        check = system.check()
        n_low, progs = lowered.n, _compiles(watch)
        result = driver.run(system, plan, rec, seconds, session, log)
        in_window = {"lowered": lowered.n - n_low,
                     "watched": _diff(_compiles(watch), progs)}
        rec.trace_summary = session.finish() if trace else None
    finally:
        system.close()
        introspection.disable_compile_watch()
    setup_s = rec.window[0] - t_start
    values = dict(result["values"], setup_s=setup_s)

    # -- the line ---------------------------------------------------------------
    metrics = {}
    if trace:
        for m in manifest.metrics_of(bench, workload, "per_layer"):
            spec = manifest.layer_metric(m["name"])
            v = manifest.module("readers", spec["reader"]).read(rec, spec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in manifest.metrics_of(bench, workload, "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    device = device_report(devices, all_devices)
    no_compiles = in_window["lowered"] == 0 and not in_window["watched"]
    line = {"correct": bool(check["ok"] and result["ok"] and no_compiles),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device}
    if trace and rec.trace_summary:
        ts = rec.trace_summary
        device["busy_s"], device["window_s"] = ts["busy_s"], ts["window_s"]
        line["breakdown"] = {"device_ops": ts["device_ops"],
                             "idle_gaps": ts["idle_gaps"]}
    log("info " + json.dumps({
        "timing": getattr(system, "timing", {}), "check": check,
        "compiles_in_window": in_window, "driver": result["info"],
        "end_to_end_of_this_run": values,
        "trace_cost_s": session.cost_s}, default=str))
    out(json.dumps(line))
    return 0


def _compiles(watch) -> dict:
    return {k: v["compiles"]
            for k, v in watch.snapshot(False)["programs"].items()}


def _diff(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--traffic-override", type=json.loads, default=None,
                    help="JSON laid over the traffic file, for the rate "
                         "sweep only (perfbench/README.md)")
    a = ap.parse_args(argv)
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                    traffic_override=a.traffic_override, t_start=T_START)


if __name__ == "__main__":
    sys.stdout.flush()
    code = main()
    sys.stdout.flush()
    # daemon threads of the killed front end may still sit in a socket
    # read; nothing of theirs is worth waiting for
    os._exit(code)
