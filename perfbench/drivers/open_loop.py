"""Open-loop serving traffic: one general generator reading a mix's
parameters, and one client thread that sends on schedule and reads every
stream.

Every run offers the SAME work.  Lengths and gaps are the
distribution's quantiles on an even grid (n values cut it into n equal
shares).  They are laid out once, from the mix's own ``order_seed``, so
that every 8 consecutive requests hold one value from each eighth of each
distribution; that sequence, one window long, repeats as a cycle.  The
run's seed picks where in the cycle the run starts and draws the token
ids.  So every window holds exactly one whole cycle, any stretch of it
carries the same mix, and the spread between runs is the system's, not
the sample's.

Times: a request is DUE at a fixed time whatever the system does; its
time to first token counts from then, so a stalled client or server shows
as latency, and how late the generator ran is printed.  The window opens
``ramp_s`` after the first request is due and is ``--seconds`` long; the
same traffic runs on for ``drain_s`` after it so that requests due late
in the window finish under the same load.
"""
from __future__ import annotations

import json
import math
import selectors
import socket
import statistics
import threading
import time

from perfbench import stats

MIN_TOKENS_FOR_GAP = 9        # a first token and one full decode window


# -- the generator --------------------------------------------------------------

def _grid(n):
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: dict, n: int):
    """n whole lengths: the quantiles of ``spec``'s distribution on an
    even grid, clipped to [min, max]."""
    dist = spec["dist"]
    if dist == "constant":
        vals = [spec["value"]] * n
    elif dist == "lognormal":
        nd = statistics.NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
                for u in _grid(n)]
    elif dist == "uniform":
        vals = [spec["min"] + (spec["max"] - spec["min"]) * u
                for u in _grid(n)]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", float("inf"))
    return [int(min(max(round(v), lo), hi)) for v in vals]


def gaps(arrival: dict, n: int):
    """n gaps between arrivals with mean 1 / rate: exponential quantiles
    (``poisson``) or equal (``constant``)."""
    rate = float(arrival["rate_rps"])
    if arrival["process"] == "constant":
        return [1.0 / rate] * n
    if arrival["process"] == "poisson":
        raw = [-math.log(1.0 - u) for u in _grid(n)]
        scale = n / rate / sum(raw)
        return [g * scale for g in raw]
    raise ValueError(f"unknown arrival process {arrival['process']!r}")


def on_off(due, burst):
    """Arrivals squeezed into ``on_s`` of every ``on_s + off_s`` (the
    mean rate stays the mix's): time inside the on-phases runs faster by
    the duty cycle, and off-phases are skipped."""
    on, off = float(burst["on_s"]), float(burst["off_s"])
    duty = on / (on + off)
    out = []
    for t in due:
        busy = t * duty                      # on-time used up by then
        out.append(busy + math.floor(busy / on) * off)
    return out


def spread_out(values, rng, block=8):
    """The sorted ``values`` in an order in which every ``block``
    consecutive ones hold one from each of ``block`` equal shares of
    the distribution."""
    import numpy as np
    order = sorted(range(len(values)), key=values.__getitem__)
    cols = [rng.permutation(part) for part in np.array_split(order, block)]
    out = []
    for j in range(max(len(c) for c in cols)):
        row = [int(c[j]) for c in cols if j < len(c)]
        rng.shuffle(row)
        out.extend(row)
    return [values[i] for i in out]


def make_requests(traffic: dict, seed: int, seconds: float, vocab: int):
    """The requests of one run: dicts of due (seconds from the first),
    prompt (token ids) and max_tokens, over ramp + window + drain."""
    import numpy as np
    arrival = traffic["arrival"]
    rate = float(arrival["rate_rps"])
    cycle = max(int(round(rate * seconds)), 1)
    horizon = traffic["ramp_s"] + seconds + traffic["drain_s"]
    total = max(int(round(rate * horizon)), 1)
    order = np.random.default_rng(int(traffic.get("order_seed", 0)))
    g = spread_out(gaps(arrival, cycle), order)
    plen = spread_out(lengths(traffic["prompt_len"], cycle), order)
    olen = spread_out(lengths(traffic["output_len"], cycle), order)
    rng = np.random.default_rng(int(seed))
    phase = int(rng.integers(cycle))
    at = [(phase + k) % cycle for k in range(total)]
    due = stats.due_times([g[j] for j in at])
    if arrival.get("burst"):
        due = on_off(due, arrival["burst"])
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [rng.integers(0, vocab, size=shared["len"]).tolist()
                    for _ in range(shared["groups"])]
    out = []
    for i, j in enumerate(at):
        ids = rng.integers(0, vocab, size=plen[j]).tolist()
        if prefixes and rng.random() < shared["share"]:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            ids = (pre + ids)[:max(plen[j], len(pre) + 1)]
        out.append({"i": i, "due": due[i], "prompt": ids,
                    "max_tokens": olen[j]})
    return out


# -- the client -----------------------------------------------------------------

class Stream:
    """One request's connection: a chunked NDJSON response read
    incrementally."""

    def __init__(self, req, sock, t_sent):
        self.req, self.sock, self.t_sent = req, sock, t_sent
        self.buf = b""
        self.headers_done = False
        self.status = None
        self.token_times = []
        self.final = None
        self.closed = False
        self.error = None

    def feed(self, data: bytes, now: float):
        self.buf += data
        if not self.headers_done:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = self.buf[:end].split(b"\r\n")
            self.status = int(head[0].split()[1])
            self.buf = self.buf[end + 4:]
            self.headers_done = True
        if self.status != 200:
            return                     # an error body; the status is enough
        while True:
            eol = self.buf.find(b"\r\n")
            if eol < 0:
                return
            size = int(self.buf[:eol], 16)
            if size == 0:
                self.closed = True
                return
            if len(self.buf) < eol + 2 + size + 2:
                return
            payload = self.buf[eol + 2:eol + 2 + size]
            self.buf = self.buf[eol + 2 + size + 2:]
            for line in payload.split(b"\n"):
                if not line.strip():
                    continue
                ev = json.loads(line)
                self.token_times.extend([now] * len(ev.get("tokens") or ()))
                if ev.get("done"):
                    self.final = ev

    @property
    def failed(self) -> bool:
        if self.error or (self.status is not None and self.status != 200):
            return True
        return self.final is not None and \
            self.final.get("state") != "finished"

    @property
    def finished(self) -> bool:
        return self.final is not None or self.failed


def _request_bytes(host, req):
    body = json.dumps({"id": f"pb-{req['i']}", "prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "stream": True}).encode()
    return (f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
            ).encode() + body


def run_client(url, requests, t0, t_stop, stop_when=None):
    """Send each request when due (``t0 + due``) and read all streams,
    on this thread, until ``t_stop`` or ``stop_when(streams)``.  Returns
    the streams, sent or not yet answered alike."""
    host = url.split("//", 1)[1]
    addr = (host.rsplit(":", 1)[0], int(host.rsplit(":", 1)[1]))
    sel = selectors.DefaultSelector()
    streams, nxt = [], 0
    try:
        while True:
            now = time.perf_counter()
            while nxt < len(requests) and t0 + requests[nxt]["due"] <= now:
                req = requests[nxt]
                nxt += 1
                try:
                    sock = socket.create_connection(addr, timeout=5.0)
                    sock.sendall(_request_bytes(host, req))
                    sock.setblocking(False)
                except OSError as e:
                    st = Stream(req, None, time.perf_counter())
                    st.error = repr(e)
                    streams.append(st)
                    continue
                st = Stream(req, sock, time.perf_counter())
                sel.register(sock, selectors.EVENT_READ, st)
                streams.append(st)
                now = time.perf_counter()
            if now >= t_stop or (stop_when and stop_when(streams, now)):
                break
            wait = t_stop - now
            if nxt < len(requests):
                wait = min(wait, t0 + requests[nxt]["due"] - now)
            for key, _ in sel.select(max(min(wait, 0.05), 0.0)):
                st = key.data
                try:
                    data = st.sock.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError as e:
                    data, st.error = b"", repr(e)
                now = time.perf_counter()
                if data:
                    st.feed(data, now)
                if not data or st.closed:
                    if not data and not st.finished:
                        st.error = st.error or "closed before the end"
                    sel.unregister(st.sock)
                    st.sock.close()
                    st.sock = None
    finally:
        for st in streams:
            if st.sock is not None:
                st.sock.close()
                st.sock = None
        sel.close()
    return streams


# -- the driver -----------------------------------------------------------------

def prepare(system, traffic, seed, seconds, rehearse):
    reqs = make_requests(traffic, seed, seconds, system.vocab)
    for r in reqs:
        if len(r["prompt"]) + r["max_tokens"] > system.max_len:
            raise ValueError(f"request {r['i']} needs "
                             f"{len(r['prompt']) + r['max_tokens']} "
                             f"positions, the engine has {system.max_len}")
    return {"requests": reqs}


def run(system, plan, rec, seconds, session, log):
    traffic = rec.context["traffic"]
    reqs = plan["requests"]
    ramp, drain = float(traffic["ramp_s"]), float(traffic["drain_s"])
    t0 = time.perf_counter() + 0.2
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    rec.window = (w0, w1)
    helpers = []

    def at(t, fn):
        """Run ``fn`` at time ``t`` off the client thread (a snapshot
        waits for the scheduler's lock, which a decode window holds)."""
        th = threading.Timer(max(t - time.perf_counter(), 0.0), fn)
        th.daemon = True
        th.start()
        helpers.append(th)

    if rec.trace:
        drain = 0.0           # the traced run's tails are not judged
        at(w0, lambda: rec.snapshots.__setitem__("start",
                                                 system.snapshot()))
        at(w1, lambda: rec.snapshots.__setitem__("end", system.snapshot()))
        session.schedule(w1 - session.seconds - 2.0)

    in_window = [r["i"] for r in reqs if w0 <= t0 + r["due"] < w1]

    def all_answered(streams, now):
        if now < w1:
            return False
        done = {s.req["i"] for s in streams if s.finished}
        return all(i in done for i in in_window)

    streams = run_client(system.url, reqs, t0, w1 + drain, all_answered)
    t_end = time.perf_counter()
    for th in helpers:
        th.join(timeout=30.0)

    # -- what the client saw ---------------------------------------------------
    by_i = {s.req["i"]: s for s in streams}
    window_tokens = sum(1 for s in streams for t in s.token_times
                        if w0 <= t < w1)
    ttft, unanswered, failed = [], 0, 0
    for i in in_window:
        s = by_i.get(i)
        due = t0 + reqs[i]["due"]
        if s is None or s.failed:
            failed += 1
            continue
        if s.token_times:
            ttft.append(s.token_times[0] - due)
        else:
            unanswered += 1
            ttft.append(t_end - due)       # at least this long
    tpot = [stats.mean_gap(s.token_times) for s in streams
            if s.token_times and w0 <= s.token_times[0] < w1
            and len(s.token_times) >= MIN_TOKENS_FOR_GAP and not s.failed]
    late = [s.t_sent - (t0 + s.req["due"]) for s in streams]
    vals = {"serve_tokens_per_s": window_tokens / seconds}
    if ttft:
        vals["ttft_p95_ms"] = 1e3 * stats.percentile(ttft, 95)
    if tpot:
        vals["tpot_p95_ms"] = 1e3 * stats.percentile(tpot, 95)
    rec.values.update(vals)

    thirds = []
    for k in range(3):
        a, b = w0 + k * seconds / 3, w0 + (k + 1) * seconds / 3
        tt = [s.token_times[0] - (t0 + s.req["due"]) for s in streams
              if a <= t0 + s.req["due"] < b and s.token_times]
        waiting = sum(1 for s in streams
                      if s.t_sent <= b and
                      (not s.token_times or s.token_times[0] > b))
        thirds.append({"ttft_median_s": stats.median(tt) if tt else None,
                       "n": len(tt), "unanswered_at_end_of_third": waiting})
    finished = [s for s in streams if s.final is not None and not s.failed]
    info = {
        "requests_due_in_window": len(in_window),
        "requests_sent": len(streams),
        "requests_finished": len(finished),
        "requests_finished_per_s_whole_run": len(finished) / (t_end - t0),
        "unanswered_in_window": unanswered,
        "failed_any": sum(1 for s in streams if s.failed),
        "errors": [s.error or s.status or s.final for s in streams
                   if s.failed][:5],
        "window_output_tokens": window_tokens,
        "prompt_tokens_mean": statistics.fmean(
            len(r["prompt"]) for r in reqs),
        "output_tokens_mean": statistics.fmean(
            r["max_tokens"] for r in reqs),
        "ttft_s": {"n": len(ttft),
                   "median": stats.median(ttft) if ttft else None},
        "tpot_s": {"n": len(tpot),
                   "median": stats.median(tpot) if tpot else None},
        "generator_lateness_p95_ms":
            1e3 * stats.percentile(late, 95) if late else None,
        "by_third": thirds,
        "drain_used_s": t_end - w1,
    }
    return {"attempted": len(in_window), "failed": failed, "ok": True,
            "values": vals, "info": info}
