"""Drive the program under test through one run of one cell.

The program is reached only through its public entry points:
``repro.launch.serve.build_engine`` (paged fp8 KV, batched prefill, and
the configuration's ``engine`` block) and
``repro.serving.runtime.AsyncServeRuntime`` (depth 1) over ``Gateway``.
A client receives each token in the request's ``stream_cb``, which the
runtime's backlog thread calls; the harness stamps it there. Everything
that turns those stamps, counters and the trace into numbers lives in the
yardstick modules beside this one, none of which imports the program: the
trace reduction, the metric readers (``metrics/<name>.py``) and the
reference module that the configuration names.
"""
from __future__ import annotations

import dataclasses
import gc
import queue
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from chipbench import peaks, trace_names, trace_reduce
from chipbench import traffic as traffic_mod
from chipbench.modelcfg import ROOT, load_config, load_module, reference
from chipbench.reference import Seq

#: Threads that hand requests to the runtime: ``submit`` blocks until the
#: dispatch thread binds the request, so a few in parallel keep the
#: generator on time when several requests are due in one tick.
SUBMITTERS = 8
#: Seconds of the window traced with ``--trace 1``, after a second's lead.
TRACE_S = 3.0
#: How long after the window closes a request due in it may still take to
#: give its first token before it counts as failed.
LATE_S = 60.0
#: Set-up's longest wait for the runtime to take one request: while it
#: compiles for a new shape, the dispatch thread takes none.
WARM_S = 1200.0
#: Requests compared with the reference: the longest finished one and
#: others drawn from the seed.
SAMPLE = 8
TRACE_DIR = ROOT.parent / ".chipbench" / "trace"
#: Where the reader of per-layer metric ``<name>`` is: ``<name>.py``.
METRICS = ROOT / "metrics"
#: ``engine`` keys that ``build_engine`` takes under another name; every
#: other key of the block goes to it under its own.
ENGINE_NAMES = {"tenants": "n_adapters"}


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Client:
    """One request as its client sees it: when it was due and sent, and
    the arrival time of every token."""

    __slots__ = ("req", "due", "sent", "times", "tokens", "ticket", "done",
                 "on_done")

    def __init__(self, req: traffic_mod.Req, due: float, on_done=None):
        self.req, self.due, self.on_done = req, due, on_done
        self.sent = None
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.ticket = None
        self.done = False

    def on_token(self, _req, tok: int) -> None:
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))

    def finished(self, ticket) -> None:
        self.done = ticket.state == "done"
        if self.on_done is not None:
            self.on_done(self)


def snapshot(stats) -> Dict:
    """Every numeric field of the engine's stats record as it reads now,
    and a copy of each field that maps names to numbers (``phase_ms``)."""
    out = {}
    for f in dataclasses.fields(stats):
        v = getattr(stats, f.name)
        if isinstance(v, dict):
            out[f.name] = dict(v)
        elif isinstance(v, (int, float)):
            out[f.name] = v
    return out


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) if len(values) \
        else None


class Run:
    """One run of one cell: set-up, the measured window, the check."""

    def __init__(self, bench: dict, cell: dict, seed: int, seconds: float,
                 trace: bool, t_start: float, *, preset: str = "full",
                 config: Optional[dict] = None, traffic: Optional[dict] = None,
                 control: bool = False):
        self.bench, self.cell, self.seed = bench, cell, seed
        self.seconds, self.trace, self.t_start = seconds, trace, t_start
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.cfg = config or load_config(ROOT.parent / conf["file"])
        self.traffic = traffic or traffic_mod.load(
            ROOT / "traffic" / f"{cell['traffic']}.json")
        self.check = traffic_mod.load(
            ROOT / "checks" / f"{cell['name']}.json")
        self.preset = preset
        self.control = control
        self.dims = self.cfg["dims"]
        self.ref = reference(self.cfg["reference"])
        self.model_seed = seed % (2 ** 31 - 1)
        self.tracer: Optional[threading.Thread] = None
        self.trace_span = None

    # -- set-up ------------------------------------------------------------
    def build(self):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import build_engine
        from repro.serving.gateway import Gateway
        from repro.serving.runtime import AsyncServeRuntime
        enable_compile_cache()
        # keep every executable, however quick to compile: the program
        # compiles small ones per prompt length (its KV writes), and set-up
        # must find them all in the cache after a cell's first run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # a key build_engine does not take fails here, at set-up
        kw = {ENGINE_NAMES.get(k, k): v for k, v in self.cfg["engine"].items()}
        eng = build_engine(self.cfg["arch"], self.preset, prefill="batched",
                           kv="paged", seed=self.model_seed, **kw)
        warm = eng.warmup_aot(max_prompt_len=self.traffic["prompt_len"]["max"])
        log(f"engine built and warmed: {warm['compiles']} executables "
            f"({warm['wall_s']:.1f} s of warm-up)")
        self.eng = eng
        self.rt = AsyncServeRuntime(Gateway(eng), depth=1).start()
        self.warm_lengths()

    def warm_lengths(self) -> None:
        """One single-token request for every prompt length the traffic can
        send, through the served path: the program compiles some of its
        steps per prompt length, and none of that may fall in the window."""
        t0 = time.perf_counter()
        rng = np.random.default_rng(self.seed + 1)
        tenants = self.cfg["engine"].get("tenants", 0)
        use = tenants if self.traffic.get("adapter_share") else 0
        lengths = traffic_mod.prompt_lengths(self.traffic)
        clients = []
        for i, n in enumerate(lengths):
            t = i % (use + 1) if use else None
            req = traffic_mod.Req(-1 - i, rng.integers(
                0, self.dims.vocab, size=n, dtype=np.int32), 1,
                None if t == use else t)
            clients.append(Client(req, 0.0))
        with ThreadPoolExecutor(max_workers=SUBMITTERS) as pool:
            for f in [pool.submit(self.send, c, WARM_S) for c in clients]:
                f.result()
        for c in clients:
            c.ticket.result(timeout=WARM_S)
        log(f"warmed {len(lengths)} prompt lengths in "
            f"{time.perf_counter() - t0:.1f} s")

    def send(self, client: Client, timeout: float = LATE_S) -> None:
        from repro.serving import RequestSpec, SamplingParams
        req = client.req
        client.sent = time.perf_counter()
        spec = RequestSpec(
            max_new_tokens=req.max_new, stream_cb=client.on_token,
            adapter_id=None if req.tenant is None else f"tenant-{req.tenant}")
        ticket = self.rt.submit(req.prompt.tolist(), spec, SamplingParams(),
                                timeout=timeout)
        client.ticket = ticket
        ticket.add_done_callback(client.finished)

    # -- the loops -----------------------------------------------------------
    def closed_loop(self, stream, pool):
        """``clients`` clients, each sending its next request as soon as
        the last one completes; runs set-up's settling and the window."""
        n = self.traffic["clients"]
        done_q: "queue.Queue" = queue.Queue()
        first = traffic_mod.take(stream, n)
        traffic_mod.residual_start(first)
        clients = [Client(r, 0.0, done_q.put) for r in first]
        futs = [pool.submit(self.send, c) for c in clients]
        for f in futs:
            f.result()
        settle_end = None
        w0 = w1 = None
        give_up = time.perf_counter() + LATE_S
        while True:
            now = time.perf_counter()
            if settle_end is None and all(c.tokens for c in clients[:n]):
                settle_end = now + self.traffic["settle_s"]
            if settle_end is None and now > give_up:
                raise RuntimeError("the first requests of the clients did not "
                                   f"all start within {LATE_S} s")
            if w0 is None and settle_end is not None and now >= settle_end:
                w0 = now
                w1 = w0 + self.seconds
                self.window_start(w0)
            if w1 is not None and now >= w1:
                break
            timeout = 0.05 if w1 is None else max(min(w1 - now, 0.05), 0)
            try:
                c = done_q.get(timeout=timeout)
            except queue.Empty:
                continue
            nxt = Client(next(stream), 0.0, done_q.put)
            nxt.due = time.perf_counter()
            clients.append(nxt)
            pool.submit(self.send, nxt)
        return clients, w0, w1

    def open_loop(self, stream, pool):
        """Poisson arrivals at the traffic's fixed rate; the window opens
        after ``lead_in_s`` seconds of arrivals and closes ``seconds``
        later, and sending goes on until every request due in it has had
        its first token (or ``LATE_S`` passes)."""
        t0 = time.perf_counter() + 0.05
        w0 = t0 + self.traffic["lead_in_s"]
        w1 = w0 + self.seconds
        clients: List[Client] = []
        opened = False
        while True:
            req = next(stream)
            due = t0 + req.due
            while True:
                now = time.perf_counter()
                if not opened and now >= w0:
                    opened = True
                    self.window_start(w0)
                if now >= due:
                    break
                time.sleep(min(due - now, 0.01))
            c = Client(req, due)
            clients.append(c)
            pool.submit(self.send, c)
            if due >= w1:
                waiting = [c for c in clients
                           if w0 <= c.due < w1 and not c.tokens
                           and not (c.ticket is not None and c.ticket.terminal)]
                if not waiting or now > w1 + LATE_S:
                    break
        return clients, w0, w1

    # -- tracing inside the window --------------------------------------------
    def window_start(self, w0: float) -> None:
        self.stats = {"start": snapshot(self.eng.stats)}
        mem = self.devices[0].memory_stats() or {}
        log(f"set-up done at {w0 - self.t_start:.2f} s; window opens with "
            f"{mem.get('bytes_in_use')} bytes in use on the device (peak so "
            f"far {mem.get('peak_bytes_in_use')})")
        if self.trace:
            self.tracer = threading.Thread(
                target=self.trace_window,
                args=(w0 + min(1.0, self.seconds / 4),
                      min(TRACE_S, self.seconds / 2)),
                name="chipbench-trace")
            self.tracer.start()

    def trace_window(self, at: float, length: float) -> None:
        """Trace ``length`` seconds from ``at``, off the generator's
        thread (starting and stopping the profiler blocks for a while).
        The window is marked in the trace, so that its reduction covers the
        same interval as the tokens counted between ``t0`` and ``t1``."""
        import jax
        time.sleep(max(at - time.perf_counter(), 0))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        t0 = (time.perf_counter(), time.time())
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            time.sleep(length)
        t1 = (time.perf_counter(), time.time())
        jax.profiler.stop_trace()
        self.trace_span = (t0, t1)

    # -- the run ----------------------------------------------------------------
    def run(self, devices) -> dict:
        import jax
        self.devices = devices
        self.build()
        vocab = self.dims.vocab
        stream = traffic_mod.requests(
            self.traffic, self.seed, vocab, self.cfg["engine"].get("tenants", 0))
        pool = ThreadPoolExecutor(max_workers=SUBMITTERS,
                                  thread_name_prefix="chipbench-client")
        try:
            if self.traffic["loop"] == "closed":
                clients, w0, w1 = self.closed_loop(stream, pool)
            else:
                clients, w0, w1 = self.open_loop(stream, pool)
            if self.tracer is not None:
                self.tracer.join()
            s0 = self.stats["start"]
            s1 = self.stats["end"] = snapshot(self.eng.stats)
            counters = {"ticks": s1["ticks"] - s0["ticks"],
                        "tokens_out": s1["tokens_out"] - s0["tokens_out"],
                        "slots": self.eng.max_slots,
                        "jit_compiles": s1["jit_compiles"],
                        "aot_fallbacks": s1["aot_fallbacks"]}
        finally:
            pool.shutdown(wait=True)
            self.rt.close(raise_on_poison=False)
        poisoned = self.rt.exception
        stats = devices[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        records = self.records(clients)
        del self.rt, self.eng
        gc.collect()
        jax.clear_caches()
        result = self.measure(clients, w0, w1, counters)
        checks, ok = self.correctness(clients, w0, w1)
        failed = self.failures(clients, w0, w1)
        if poisoned is not None:
            log(f"the runtime failed: {poisoned!r}")
        correct = ok and not failed and poisoned is None
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory_peak}
        out = {"correct": correct,
               "attempted": sum(1 for c in clients if w0 <= c.due < w1)
               if self.traffic["loop"] == "open" else
               sum(1 for c in clients if c.sent is not None
                   and w0 <= c.sent < w1),
               "failed": len(failed), "metrics": {}, "device": device}
        if self.trace:
            red = self.reduce_trace()
            out["metrics"] = self.per_layer(records, counters, red)
            if red is not None:
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                out["breakdown"] = {
                    "device_ops": trace_reduce.top(red["ops"]),
                    "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
        else:
            out["metrics"] = result
        log(f"jit compiles after warm-up: {counters['jit_compiles']}, AOT "
            f"fallbacks: {counters['aot_fallbacks']}")
        for name, c in checks.items():
            log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
        out["checks"] = checks
        return out

    # -- end-to-end metrics ------------------------------------------------------
    def measure(self, clients, w0, w1, counters) -> Dict:
        names = [m for m in self.bench["end_to_end"]
                 if self.cell["name"] in m.get("workloads",
                                               [self.cell["name"]])]
        vals: Dict[str, Optional[float]] = {"setup_s": w0 - self.t_start}
        toks = sum(1 for c in clients for t in c.times if w0 <= t < w1)
        vals["output_tok_s"] = toks / (w1 - w0)
        gaps = [(b - a) * 1e3 for c in clients
                for a, b in zip(c.times, c.times[1:]) if w0 <= b < w1]
        vals["itl_p99_ms"] = percentile(gaps, 99)
        due = [c for c in clients if w0 <= c.due < w1 and c.times]
        vals["ttft_p90_ms"] = percentile(
            [(c.times[0] - c.due) * 1e3 for c in due], 90)
        late = [(c.sent - c.due) * 1e3 for c in clients
                if c.sent is not None and c.due and w0 <= c.due < w1]
        if late:
            log(f"load generator lateness over {len(late)} requests: p50 "
                f"{percentile(late, 50):.3f} ms, p99 {percentile(late, 99):.3f}"
                f" ms, max {max(late):.3f} ms")
        log(f"window {w1 - w0:.2f} s: {toks} tokens received, {len(gaps)} "
            f"inter-token gaps, {len(due)} requests due with a first token")
        out = {}
        for m in names:
            v = vals.get(m["name"])
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    def failures(self, clients, w0, w1) -> List[Client]:
        """Requests due (open loop) or sent (closed loop) in the window that
        were refused, errored, or (open loop) never gave a first token."""
        bad = []
        for c in clients:
            t = c.due if self.traffic["loop"] == "open" else (c.sent or 0)
            if not w0 <= t < w1:
                continue
            state = c.ticket.state if c.ticket is not None else "unsent"
            if state in ("rejected", "error", "expired", "unsent") or (
                    self.traffic["loop"] == "open" and not c.tokens):
                bad.append(c)
        if bad:
            log(f"{len(bad)} requests failed: "
                f"{[c.ticket.state if c.ticket else 'unsent' for c in bad][:10]}")
        return bad

    # -- correctness ---------------------------------------------------------------
    def correctness(self, clients, w0, w1):
        """Compare a seeded sample of the finished requests, the longest
        among them, with the plain reference: the widest gap, over every
        served token of the sample, by which its logit lies below the
        reference's best (``max_logit_gap``). With ``control`` the fp8
        control stands in the program's place: at the same positions, the
        gap of the token that it puts first. The mean gap and the number
        of tokens that are not the reference's first choice are logged."""
        limit = self.check["limit"]
        done = [c for c in clients if c.done
                and len(c.tokens) == c.req.max_new]
        if not done:
            return {"max_logit_gap": {"value": None, "limit": limit}}, False
        seqs = self.sample(done)
        t0 = time.perf_counter()
        e = self.cfg["engine"]
        out = self.ref.served_gaps(
            self.dims, self.model_seed, seqs, e["max_len"],
            tenants=e.get("tenants", 0), rank=e.get("adapter_rank", 8),
            control=self.control, n_out=self.traffic["output_len"]["max"])
        self.gaps = {"program": np.concatenate(out["gap"])}
        if self.control:
            self.gaps["control"] = np.concatenate(out["control_gap"])
        log(f"reference over {len(seqs)} requests, {len(self.gaps['program'])}"
            f" served tokens, {time.perf_counter() - t0:.1f} s")
        for who, g in self.gaps.items():
            log(f"{who}: widest gap {g.max():.6f}, mean gap {g.mean():.6g}, "
                f"{int((g > 0).sum())} tokens not the reference's choice")
        value = float(self.gaps["control" if self.control else "program"]
                      .max())
        checks = {"max_logit_gap": {"value": value, "limit": limit}}
        return checks, value <= limit

    def sample(self, done: List[Client]) -> List[Seq]:
        rng = np.random.default_rng(self.seed)
        longest = max(range(len(done)), key=lambda i: (
            len(done[i].tokens), len(done[i].req.prompt)))
        rest = [i for i in range(len(done)) if i != longest]
        pick = [longest] + list(rng.choice(
            rest, size=min(SAMPLE - 1, len(rest)), replace=False))
        return [Seq(done[i].req.prompt, np.asarray(done[i].tokens, np.int32),
                    done[i].req.tenant) for i in pick]

    # -- per-layer metrics -----------------------------------------------------------
    @staticmethod
    def records(clients) -> Dict:
        """What the per-layer readers see of the clients: every token's
        arrival, the context its step attended (prompt + earlier tokens +
        itself) and whether it carried an adapter."""
        return {"tokens": [(t, len(c.req.prompt) + j, c.req.tenant is not None)
                           for c in clients for j, t in enumerate(c.times)]}

    def reduce_trace(self) -> Optional[Dict]:
        path = trace_reduce.latest_xplane(str(TRACE_DIR))
        if path is None or self.trace_span is None:
            log("no trace was recorded")
            return None
        t0 = time.perf_counter()
        red = trace_names.reduce_file(path)
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if not red["marked"]:
            log("the trace holds no mark of its window")
            return None
        (p0, _), (p1, _) = self.trace_span
        log(f"trace: {red['devices']} device(s), busy {red['busy_s']:.4f} s "
            f"of {red['window_s']:.4f} s marked ({p1 - p0:.4f} s on the "
            f"host's clock)")
        return red

    def per_layer(self, records, counters, red) -> Dict:
        ctx = {"dims": self.dims, "trace": red, "counters": counters,
               "records": records, "cell": self.cell["name"],
               "window": self.trace_span, "engine": self.cfg["engine"],
               "config": self.cfg, "stats": self.stats}
        if red is not None and red["devices"]:
            ctx["peaks"] = peaks.peaks_for(self.devices[0].device_kind)
        out = {}
        for m in self.bench["per_layer"]:
            if self.cell["name"] not in m.get("workloads", [self.cell["name"]]):
                continue
            mod = load_module(METRICS / f"{m['name']}.py")
            v = mod.read(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices, t_start: float, **kw) -> dict:
    return Run(bench, cell, seed, seconds, trace, t_start, **kw).run(devices)
