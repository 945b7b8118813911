"""Async disaggregated serving runtime: dispatch-ahead + backlog threads.

The synchronous gateway drives ``ServeEngine.tick()`` inline: host
bookkeeping (sampling vectors, admission, emit, metrics, SLO, callbacks)
serializes with device compute every tick — the serialization point the
paper's distributed ROM-bank architecture exists to avoid, quantified by
``EngineStats.host_overhead_frac``. This runtime splits the loop:

  dispatch thread   owns the engine + scheduler. Drains a thread-safe
                    inbox (submit / cancel / barrier), then calls
                    ``tick_begin()`` — which enqueues tick N+1's jitted
                    decode+sample *before* tick N's results are read — and
                    trims the engine's pending deque to ``depth``
                    (``tick_finish()`` materializes + emits). The device
                    queue therefore always holds the next tick's work
                    while the host loops.

  backlog thread    owns every gateway-side consumer: per-request token
                    buffers (Tickets), ``on_token`` stream callbacks,
                    metrics/SLO/energy bookkeeping, gauge sampling. The
                    dispatch thread never runs a user callback; events
                    carry their dispatch-time timestamps so SLO components
                    still telescope to wall regardless of backlog delay.

  supervisor        crash propagation in the JetThread style: any
                    exception on either worker poisons the runtime —
                    in-flight requests are cancelled into a terminal error
                    state, engine pages/pins are released, and the
                    original exception re-raises from every caller-facing
                    API (submit / cancel / drain / quiesce / close). A
                    poisoned runtime never hangs a waiter.

In a ``jax.profiler`` trace each thread names its own work
(``repro.obs.names``): the dispatch thread ``serve.<phase>`` (the engine's
tick phases plus ``serve.inbox`` / ``serve.idle``), the backlog thread
``backlog.<event>``, a submitting client ``client.bind``.

Token identity: the engine's split-tick pipeline feeds in-flight slots
their unmaterialized token via a device-side overlay and offsets seeded
sampling steps by the in-flight count, so seeded/greedy async output is
bit-identical to the sync path (pinned by tests/test_async_runtime.py).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.obs.names import BACKLOG_SPANS, CLIENT_BIND

TERMINAL_STATES = ("done", "cancelled", "expired", "rejected", "error")

_STOP = object()      # backlog sentinel


class RuntimePoisoned(RuntimeError):
    """The runtime crashed: a worker thread raised, all in-flight requests
    were cancelled with a terminal error state, and the original exception
    is re-raised (chained) in every caller-facing API."""

    def __init__(self, cause: BaseException):
        super().__init__(f"serving runtime poisoned by worker exception: "
                         f"{cause!r}")
        self.cause = cause


class Ticket:
    """Thread-safe client handle for one async request.

    The dispatch thread binds the engine ``Request``; the backlog thread
    pushes tokens and the terminal state; any client thread may block in
    ``result()`` / iterate ``stream()``. All state rides one condition
    variable — no polling."""

    def __init__(self):
        self._cond = threading.Condition()
        self._tokens: List[int] = []
        self._done_cbs: List = []
        self.state = "pending"          # pending → queued → <terminal>
        self.error: Optional[BaseException] = None
        self.req = None                 # engine Request, set at bind
        self.uid: Optional[int] = None

    # -- worker-side ---------------------------------------------------------
    def _bind(self, req) -> None:
        with self._cond:
            self.req = req
            self.uid = req.uid
            if req.state == "rejected":
                self.state = "rejected"
            elif self.state == "pending":
                self.state = "queued"
            self._cond.notify_all()
        if req.state == "rejected":
            self._fire_done_cbs()

    def _push(self, tok: int) -> None:
        with self._cond:
            self._tokens.append(tok)
            self._cond.notify_all()

    def _finish(self, state: str, error: Optional[BaseException] = None
                ) -> None:
        with self._cond:
            if self.state in TERMINAL_STATES:
                return
            self.state = state
            self.error = error
            self._cond.notify_all()
        self._fire_done_cbs()

    def _fire_done_cbs(self) -> None:
        cbs, self._done_cbs = self._done_cbs, []
        for cb in cbs:
            try:
                cb(self)
            except Exception:
                pass    # client callback failures never poison the runtime

    # -- client-side ---------------------------------------------------------
    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    def add_done_callback(self, cb) -> None:
        """``cb(ticket)`` once the ticket reaches a terminal state (fires
        immediately if it already has) — the HTTP front's per-tenant
        in-flight accounting hangs off this."""
        fire = False
        with self._cond:
            if self.terminal:
                fire = True
            else:
                self._done_cbs.append(cb)
        if fire:
            try:
                cb(self)
            except Exception:
                pass

    def wait_bound(self, timeout: Optional[float] = None) -> None:
        with self._cond:
            if not self._cond.wait_for(
                    lambda: self.req is not None or self.terminal, timeout):
                raise TimeoutError("runtime did not bind the request")

    def tokens(self) -> List[int]:
        with self._cond:
            return list(self._tokens)

    def stream(self, timeout: float = 60.0):
        """Yield tokens as the backlog thread lands them; returns after the
        terminal state (raises RuntimePoisoned if that state is an error).
        ``timeout`` bounds each *wait between tokens*, not the stream."""
        i = 0
        while True:
            with self._cond:
                if not self._cond.wait_for(
                        lambda: len(self._tokens) > i or self.terminal,
                        timeout):
                    raise TimeoutError("token stream stalled")
                batch = self._tokens[i:]
                i = len(self._tokens)
                state = self.state if (self.terminal
                                       and i >= len(self._tokens)) else None
                err = self.error
            for tok in batch:
                yield tok
            if state is not None:
                if state == "error":
                    raise RuntimePoisoned(err) if err is not None \
                        else RuntimeError("request errored")
                return

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until terminal; return the full token list. Raises
        RuntimePoisoned when the runtime crashed under this request."""
        with self._cond:
            if not self._cond.wait_for(lambda: self.terminal, timeout):
                raise TimeoutError("request did not finish")
            if self.state == "error":
                raise RuntimePoisoned(self.error) if self.error is not None \
                    else RuntimeError("request errored")
            return list(self._tokens)


class AsyncServeRuntime:
    """Wrap a `Gateway` in the dispatch/backlog/supervisor thread trio.

    Use as a context manager or call ``start()`` / ``close()`` explicitly.
    ``submit`` / ``cancel`` are thread-safe (multiple client threads may
    call them concurrently); ``quiesce()`` is the barrier fuzz/tests use
    to observe a consistent engine + metrics state."""

    def __init__(self, gateway, *, depth: int = 1, inbox_limit: int = 1024,
                 gauge_every: int = 20):
        assert depth >= 0
        self.gw = gateway
        self.eng = gateway.engine
        self.depth = depth
        self.gauge_every = max(gauge_every, 1)
        self._inbox: "queue.Queue" = queue.Queue(maxsize=inbox_limit)
        self._events: "queue.Queue" = queue.Queue()
        self._tickets: Dict[int, Ticket] = {}
        self._tickets_lock = threading.Lock()
        self._poison: Optional[BaseException] = None
        self._poison_lock = threading.Lock()
        self._stop = threading.Event()
        self._started = False
        self._closed = False
        self._tick_events = 0
        self._hooks0: Dict[str, Any] = {}
        self._dispatch_thread = threading.Thread(
            target=self._dispatch_loop, name="serve-dispatch", daemon=True)
        self._backlog_thread = threading.Thread(
            target=self._backlog_loop, name="serve-backlog", daemon=True)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "AsyncServeRuntime":
        if self._started:
            return self
        self._wire_hooks()
        self._started = True
        self._dispatch_thread.start()
        self._backlog_thread.start()
        return self

    def __enter__(self) -> "AsyncServeRuntime":
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        # don't mask a client exception with the poison re-raise
        self.close(raise_on_poison=exc_type is None)
        return False

    def close(self, timeout: float = 30.0,
              raise_on_poison: bool = True) -> None:
        """Graceful shutdown: stop the dispatch loop (settling any pending
        tick), drain the backlog, join both threads; re-raise the poison
        exception if the runtime crashed."""
        if self._started and not self._closed:
            self._stop.set()
            self._dispatch_thread.join(timeout)
            self._events.put(_STOP)
            self._backlog_thread.join(timeout)
            self._unwire_hooks()
            self._closed = True
        if raise_on_poison and self._poison is not None:
            raise RuntimePoisoned(self._poison)

    @property
    def poisoned(self) -> bool:
        return self._poison is not None

    @property
    def exception(self) -> Optional[BaseException]:
        return self._poison

    def _check_poison(self) -> None:
        if self._poison is not None:
            raise RuntimePoisoned(self._poison)

    # -- client API ----------------------------------------------------------
    def submit(self, prompt: List[int], spec=None, sampling=None,
               timeout: float = 30.0) -> Ticket:
        """Thread-safe submit: enqueue for the dispatch thread, block until
        the engine Request is bound (so ``ticket.uid`` and rejection are
        known), return the Ticket."""
        self._check_poison()
        if not self._started:
            raise RuntimeError("runtime not started")
        ticket = Ticket()
        self._inbox.put(("submit", (list(prompt), spec, sampling), ticket),
                        timeout=timeout)
        try:
            with jax.profiler.TraceAnnotation(CLIENT_BIND):
                ticket.wait_bound(timeout)
        except TimeoutError:
            self._check_poison()
            raise
        self._check_poison()
        return ticket

    def cancel(self, uid: int, timeout: float = 30.0) -> bool:
        """Thread-safe cancel by uid; blocks for the dispatch thread's
        verdict (False = unknown/already finished)."""
        self._check_poison()
        box: Dict[str, bool] = {"ok": False}
        done = threading.Event()
        self._inbox.put(("cancel", uid, box, done), timeout=timeout)
        if not done.wait(timeout):
            self._check_poison()
            raise TimeoutError("cancel did not complete")
        self._check_poison()
        return box["ok"]

    def quiesce(self, timeout: float = 60.0) -> None:
        """Barrier: returns once the dispatch thread has settled every
        pending tick AND the backlog thread has processed every event
        enqueued before that point — engine state, tickets and the metrics
        registry are mutually consistent afterwards."""
        self._check_poison()
        done = threading.Event()
        self._inbox.put(("barrier", done), timeout=timeout)
        if not done.wait(timeout):
            self._check_poison()
            raise TimeoutError("quiesce barrier did not complete")
        self._check_poison()

    def drain(self, timeout: float = 300.0) -> None:
        """Block until every submitted request reached a terminal state and
        the engine is empty (then quiesce). Raises on poison/timeout."""
        deadline = time.monotonic() + timeout
        while True:
            self._check_poison()
            with self._tickets_lock:
                pending = [t for t in self._tickets.values()
                           if not t.terminal]
            busy = (len(self.eng.scheduler)
                    or any(r is not None for r in self.eng.slot_req)
                    or len(self.eng._pending))
            if not pending and not busy:
                self.quiesce(timeout=max(deadline - time.monotonic(), 1.0))
                return
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain timed out with {len(pending)} live requests")
            time.sleep(0.002)

    # -- admission (the HTTP front's budget checks) --------------------------
    def admission_check(self, prompt_len: int, max_new_tokens: int,
                        adapter_id: Optional[str] = None,
                        max_queue: int = 256) -> Optional[str]:
        """Front-door admission control against pool + adapter budgets.
        Returns a human-readable rejection reason, or None to admit. Reads
        engine ints cross-thread (point-in-time admission is inherently
        approximate; the engine's own admission is the hard gate)."""
        eng = self.eng
        if self._poison is not None:
            return "runtime poisoned"
        if len(eng.scheduler) >= max_queue:
            return "queue full"
        if adapter_id is not None:
            if eng.adapters is None or not eng.adapters.servable(adapter_id):
                return f"adapter {adapter_id!r} not servable"
        if eng.kv.supports_paging:
            need = eng.kv.pages_for(
                min(prompt_len + max_new_tokens, eng.max_len))
            if need > eng.kv.capacity_pages:
                return "context exceeds page-pool capacity"
        return None

    # -- hook wiring ---------------------------------------------------------
    def _wire_hooks(self) -> None:
        """Replace the gateway's inline engine hooks with event enqueuers:
        the dispatch thread only captures (event, timestamp); the backlog
        thread replays the gateway bookkeeping."""
        eng, ev = self.eng, self._events
        self._hooks0 = {k: getattr(eng, k) for k in
                        ("on_token", "on_done", "on_admit", "on_preempt",
                         "on_expire", "on_tick")}
        # snapshot the 1-based output index and the previous token's
        # timestamp at emit time: by backlog-replay time the engine has
        # moved on, and the gateway's live reads would misclassify
        # TTFT/TBT (see Gateway._on_token)
        eng.on_token = lambda req, tok, now: ev.put(
            ("token", req, tok, now, len(req.output), req.t_last))
        eng.on_done = lambda req: ev.put(("done", req))
        eng.on_admit = lambda req, slot: ev.put(("admit", req, slot))
        eng.on_preempt = lambda req: ev.put(("preempt", req, time.time()))
        eng.on_expire = lambda req: ev.put(("expire", req, time.time()))
        eng.on_tick = self._on_tick_dispatch

    def _unwire_hooks(self) -> None:
        for k, v in self._hooks0.items():
            setattr(self.eng, k, v)

    def _on_tick_dispatch(self, summary: Dict) -> None:
        # engine state is dispatch-thread-owned: snapshot what the energy
        # model needs here instead of letting the backlog read it racily
        summary["sram_utilization"] = self.gw._sram_utilization()
        self._events.put(("tick", summary))

    # -- dispatch thread -----------------------------------------------------
    def _dispatch_loop(self) -> None:
        eng = self.eng
        try:
            while not self._stop.is_set():
                with eng._phase("inbox"):
                    self._drain_inbox()
                if not (len(eng.scheduler)
                        or any(r is not None for r in eng.slot_req)):
                    eng._settle_pipeline()
                    with eng._phase("idle"):
                        self._drain_inbox(timeout=0.02)
                    continue
                ticks0 = eng.stats.ticks
                t0 = time.perf_counter()
                eng.tick_begin()
                while len(eng._pending) > self.depth:
                    eng.tick_finish()
                eng.stats.wall_s += time.perf_counter() - t0
                if eng.stats.ticks == ticks0:
                    # no progress: settle and re-check — a queued request
                    # nothing can admit must not busy-spin the loop
                    eng._settle_pipeline()
                    if not any(r is not None for r in eng.slot_req):
                        with eng._phase("idle"):
                            self._drain_inbox(timeout=0.02)
            # graceful stop: flush the pipeline so every sampled token is
            # emitted before the backlog drains
            eng._settle_pipeline()
        except BaseException as exc:      # noqa: BLE001 — supervisor contract
            self._poison_with(exc)
        finally:
            if self._poison is not None:
                self._cleanup_after_poison()

    def _drain_inbox(self, timeout: Optional[float] = None) -> bool:
        try:
            op = (self._inbox.get(timeout=timeout) if timeout
                  else self._inbox.get_nowait())
        except queue.Empty:
            return False
        while True:
            self._handle_op(op)
            try:
                op = self._inbox.get_nowait()
            except queue.Empty:
                return True

    def _handle_op(self, op: Tuple) -> None:
        kind = op[0]
        if kind == "submit":
            _, (prompt, spec, sampling), ticket = op
            req = self.eng.submit(prompt, spec, sampling)
            with self._tickets_lock:
                self._tickets[req.uid] = ticket
            ticket._bind(req)
            self._events.put(("submit", req))
        elif kind == "cancel":
            _, uid, box, done = op
            req = self.gw._find_req(uid)
            ok = self.eng.cancel(uid)
            if ok and req is not None:
                self._events.put(("cancel", req, time.time()))
            elif ok:
                self._events.put(("cancel", None, time.time()))
            box["ok"] = ok
            done.set()
        elif kind == "barrier":
            self.eng._settle_pipeline()
            self._events.put(("barrier", op[1]))

    # -- backlog thread ------------------------------------------------------
    def _backlog_loop(self) -> None:
        try:
            while True:
                evt = self._events.get()
                if evt is _STOP:
                    break
                with jax.profiler.TraceAnnotation(BACKLOG_SPANS[evt[0]]):
                    self._handle_event(evt)
        except BaseException as exc:      # noqa: BLE001 — supervisor contract
            self._poison_with(exc)
            self._cleanup_tickets()

    def _handle_event(self, evt: Tuple) -> None:
        gw = self.gw
        kind = evt[0]
        if kind == "token":
            _, req, tok, now, idx, t_prev = evt
            gw._on_token(req, tok, now, idx=idx, t_prev=t_prev)
            t = self._ticket(req)
            if t is not None:
                t._push(tok)
        elif kind == "done":
            gw._on_done(evt[1])
            self._finish_ticket(evt[1], "done")
        elif kind == "submit":
            gw._note_submit(evt[1])
        elif kind == "admit":
            gw._on_admit(evt[1], evt[2])
        elif kind == "preempt":
            gw._on_preempt(evt[1], now=evt[2])
        elif kind == "expire":
            gw._on_expire(evt[1], now=evt[2])
            self._finish_ticket(evt[1], "expired")
        elif kind == "cancel":
            _, req, now = evt
            if req is not None:
                gw._note_cancel(req, now=now)
                self._finish_ticket(req, "cancelled")
            else:
                gw.metrics.inc("requests_cancelled")
        elif kind == "tick":
            gw._on_tick(evt[1])
            gw.metrics.set_gauge("backlog_len", self._events.qsize())
            self._tick_events += 1
            if self._tick_events % self.gauge_every == 0:
                gw._sample_gauges()
        elif kind == "barrier":
            gw._sample_gauges()
            gw.metrics.set_gauge("backlog_len", 0)
            evt[1].set()

    def _ticket(self, req) -> Optional[Ticket]:
        with self._tickets_lock:
            return self._tickets.get(req.uid)

    def _finish_ticket(self, req, state: str) -> None:
        t = self._ticket(req)
        if t is not None:
            t._finish(state)

    # -- supervisor ----------------------------------------------------------
    def _poison_with(self, exc: BaseException) -> None:
        with self._poison_lock:
            if self._poison is not None:
                return
            self._poison = exc
        self._stop.set()

    def _cleanup_after_poison(self) -> None:
        """Dispatch-thread poison cleanup: drop unmaterialized work, cancel
        every live request, release every slot's pages and adapter pins,
        drain the scheduler, fail pending inbox ops, then error the
        tickets. Zero leaked pages/pins is asserted by the crash-injection
        tests."""
        eng = self.eng
        try:
            eng._pending.clear()
            for slot, req in list(enumerate(eng.slot_req)):
                if req is None:
                    continue
                req.state = "cancelled"
                eng.stats.cancelled += 1
                eng._release_slot(slot)
            while len(eng.scheduler):
                r = eng.scheduler.pop_next(lambda _r: True)
                if r is None:
                    break
                r.state = "cancelled"
                eng.stats.cancelled += 1
        except Exception:
            pass          # best effort — the poison still propagates
        # fail inbox ops that will never be handled
        while True:
            try:
                op = self._inbox.get_nowait()
            except queue.Empty:
                break
            if op[0] == "submit":
                op[2]._finish("error", self._poison)
            elif op[0] == "cancel":
                op[3].set()
            elif op[0] == "barrier":
                op[1].set()
        self._cleanup_tickets()
        self._events.put(_STOP)

    def _cleanup_tickets(self) -> None:
        with self._tickets_lock:
            tickets = list(self._tickets.values())
        for t in tickets:
            t._finish("error", self._poison)
