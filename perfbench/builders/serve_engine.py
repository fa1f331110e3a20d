"""A serving system through the normal path, in one process:
``LLMEngine`` -> ``Scheduler(chunked_prefill=True)`` ->
``serving/server.py``'s HTTP front end.

The benchmark's spans go round the calls into each layer, from here:
``pb.engine.step`` round ``engine.step`` and ``pb.sched.admit`` round
the scheduler's admission.  Nothing inside the program is changed.
"""
from __future__ import annotations

import json
import time
import urllib.request

from perfbench import reference
from perfbench.builders import models


def build(cfg, traffic, seed, rec, rehearse, log):
    return ServeSystem(cfg, seed, rec, rehearse, log)


class ServeSystem:
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
        self.model = models.make_model(sz, seed, eng["max_len"])
        t1 = time.perf_counter()
        self.engine = LLMEngine(self.model, dtype=getattr(jnp, sz["dtype"]),
                                **eng)
        self.sched = Scheduler(self.engine, **sz["scheduler"])
        self._wrap()
        self.fe = start_http_frontend(self.sched, request_timeout=900.0)
        self.url = self.fe.url
        self.vocab = sz["vocab_size"]
        self.max_len = eng["max_len"]
        jax.block_until_ready(self.engine.cache.k_pages)
        self.timing = {"weights_s": t1 - t0,
                       "engine_build_s": time.perf_counter() - t1}
        log(f"serve_engine: {sz['name']} weights {t1 - t0:.1f}s, engine "
            f"up in {time.perf_counter() - t1:.1f}s at {self.url}, "
            f"engine {eng}, scheduler {sz['scheduler']}")

    # -- the benchmark's own spans ---------------------------------------------
    def _wrap(self):
        rec, engine, sched = self.rec, self.engine, self.sched
        step, admit = engine.step, sched._admit

        def traced_step():
            with rec.span("pb.engine.step"):
                out = step()
            if rec.trace:
                # counts of the engine's snapshot, read where the
                # scheduling thread already holds its lock
                free = engine.free_slots()
                live = len(engine._active)
                rec.sample("engine.occupancy",
                           100.0 * live / max(live + free, 1))
            return out

        def traced_admit(*a, **kw):
            with rec.span("pb.sched.admit"):
                return admit(*a, **kw)

        engine.step = traced_step
        sched._admit = traced_admit

    def snapshot(self) -> dict:
        return self.sched.metrics_snapshot()

    # -- one request, for warm-up and probes -----------------------------------
    def stream(self, rid, prompt, max_tokens, timeout=900.0):
        body = json.dumps({"id": rid, "prompt": [int(t) for t in prompt],
                           "max_tokens": int(max_tokens),
                           "stream": True}).encode()
        req = urllib.request.Request(
            self.url + "/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        toks, final = [], None
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for line in resp:
                line = line.strip()
                if not line:
                    continue
                ev = json.loads(line)
                toks.extend(ev.get("tokens") or [])
                if ev.get("done"):
                    final = ev
        if final is None or final.get("state") != "finished":
            raise RuntimeError(f"request {rid} did not finish: {final}")
        return toks

    def warm(self, plan):
        """Every program the traffic uses: the mixed step and each
        decode-window bucket, walked by one request whose prompt crosses
        a page and whose budget runs 2 x steps_per_sync tokens (windows
        of k, k/2, ... 1)."""
        import numpy as np
        t0 = time.perf_counter()
        page = self.sz["engine"]["page_size"]
        k = int(self.sz["engine"].get("steps_per_sync", 1))
        rng = np.random.default_rng(self.seed)
        prompt = rng.integers(0, self.vocab, size=page + 7).tolist()
        n = len(self.stream("pb-warm", prompt, 2 * k))
        self.timing["warmup_s"] = time.perf_counter() - t0
        self.log(f"serve_engine: warm-up request, {n} tokens in "
                 f"{self.timing['warmup_s']:.1f}s")

    def check(self) -> dict:
        """Seeded probes through the normal path, then the plain
        reference teacher-forced over prompt + served tokens."""
        import numpy as np
        t0 = time.perf_counter()
        p = self.sz["probe"]
        rng = np.random.default_rng(self.seed + 1)
        params = reference.canonical(self.sz["arch"],
                                     self.model.raw_state_dict(),
                                     self.sz["num_hidden_layers"])
        rows = []
        for i in range(p["prompts"]):
            prompt = rng.integers(0, self.vocab,
                                  size=p["prompt_len"]).tolist()
            served = self.stream(f"pb-probe-{i}", prompt, p["new_tokens"])
            ref = reference.logits(params, self.sz, prompt + served[:-1])
            rows.append(reference.judge_served(ref, len(prompt), served))
        self.timing["probe_s"] = time.perf_counter() - t0
        ok = all(r["ok"] for r in rows)
        self.log(f"serve_engine: probes ok={ok} {rows} in "
                 f"{self.timing['probe_s']:.1f}s")
        return {"ok": ok, "probes": rows}

    def close(self):
        """Stop the front end and let go of the engine: handler threads of
        requests cut at the window's end sit in a queue read for minutes
        and would keep the pools alive through the front end."""
        fe = self.fe
        fe.kill()
        for name, th in (("loop", fe._loop_thread),
                         ("http", fe._http_thread)):
            if th.is_alive():
                raise RuntimeError(f"the front end's {name} thread did "
                                   f"not stop")
        fe.target = None
        del self.engine.step, self.sched._admit      # the wrappers' cycles
        self.engine = self.sched = self.model = self.fe = None

