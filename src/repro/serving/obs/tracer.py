"""Chrome ``trace_event`` tracer for the serving tick loop.

One `Tracer` records three kinds of activity:

  * **phase spans** (`span`): nested complete ("X") events on a per-engine
    "tick" track — the engine wraps each tick and its phases (those of
    ``repro.obs.names.PHASES``: schedule / admit / prefill / kv_write /
    decode / sample / commit / emit / ...) so a captured trace shows
    exactly where a tick's time goes;
  * **request lifecycle tracks** (`lifecycle`): each request uid gets its
    own track; every state (queued → prefilling → decoding) is one "X"
    span from state entry to exit, terminal states (done / cancelled /
    expired) and preemption edges land as instant ("i") events;
  * **instants and counters** (`instant` / `counter`): one-off markers —
    the engine's jit-recompile events (with the offending shape bucket)
    and the ``tick_gap_ms`` counter series ride here.

Export is the Chrome ``trace_event`` format (ts/dur in microseconds):
``dump(path)`` writes strict JSONL (one event object per line — what the
CI validity check parses) for ``*.jsonl`` paths and a
``{"traceEvents": [...]}`` JSON document (the classic Perfetto /
chrome://tracing container) for anything else. Perfetto's JSON tokenizer
accepts both. Events are sorted by timestamp at dump time, so child spans
(emitted at exit, before their parent) come out ts-monotonic.

``Tracer(ring=N)`` keeps only the newest N events (metadata and still-open
lifecycle state survive eviction), so long soaks stay bounded.
``Tracer(enabled=False)`` — the engine default — is a null object: every
`span()` call returns one shared no-op context manager and nothing is
allocated or recorded.

`CompileWatch` wraps a jitted callable and reports cache growth: every
compile (including the first) bumps a counter and emits an instant event
naming the argument shape bucket that triggered it — the recompile-stall
signal for the AOT-warmup roadmap item.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

#: Request lifecycle states that end a request's track.
TERMINAL_STATES = ("done", "cancelled", "expired", "rejected")

#: tid of the engine's tick/phase track inside its process group.
TICK_TID = 0


class _NullSpan:
    """Shared no-op context manager handed out by a disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Open span: records one complete ("X") event when it exits."""
    __slots__ = ("tracer", "name", "pid", "tid", "args", "t0")

    def __init__(self, tracer: "Tracer", name: str, pid: int, tid: int,
                 args: Optional[Dict[str, Any]]):
        self.tracer = tracer
        self.name = name
        self.pid = pid
        self.tid = tid
        self.args = args
        self.t0 = tracer._now_us()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        t1 = self.tracer._now_us()
        self.tracer._push({"ph": "X", "name": self.name, "cat": "phase",
                           "ts": self.t0, "dur": t1 - self.t0,
                           "pid": self.pid, "tid": self.tid,
                           **({"args": self.args} if self.args else {})})
        return False


class Tracer:
    def __init__(self, enabled: bool = True, ring: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.enabled = enabled
        self.ring = ring
        self._clock = clock
        self._t0 = clock()
        # ring=N keeps the newest N events; metadata (process/thread names)
        # lives separately so Perfetto track names survive eviction
        self.events: "collections.deque" = collections.deque(maxlen=ring)
        self._meta: List[Dict[str, Any]] = []
        self._pids = itertools.count(1)
        self._proc_names: Dict[int, str] = {}
        # per-(pid, uid) open lifecycle state: state name + entry ts
        self._open_life: Dict[tuple, tuple] = {}
        self._named_tids: set = set()

    # -- clock / storage ----------------------------------------------------
    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _push(self, evt: Dict[str, Any]) -> None:
        self.events.append(evt)

    # -- track registry -----------------------------------------------------
    def register(self, name: str) -> int:
        """Allocate a process group (one per engine) so several traced
        engines in one process don't interleave their tick tracks."""
        pid = next(self._pids)
        self._proc_names[pid] = name
        self._meta.append({"ph": "M", "name": "process_name", "pid": pid,
                           "tid": 0, "ts": 0, "args": {"name": name}})
        self._meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": TICK_TID, "ts": 0, "args": {"name": "tick"}})
        return pid

    def _name_tid(self, pid: int, tid: int, name: str) -> None:
        if (pid, tid) not in self._named_tids:
            self._named_tids.add((pid, tid))
            self._meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                               "tid": tid, "ts": 0, "args": {"name": name}})

    # -- recording ----------------------------------------------------------
    def span(self, name: str, pid: int = 1, tid: int = TICK_TID,
             **args):
        """Context manager recording a complete event on exit. Disabled
        tracers return one shared no-op singleton (nothing allocated)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, pid, tid, args or None)

    def instant(self, name: str, pid: int = 1, tid: int = TICK_TID,
                **args) -> None:
        if not self.enabled:
            return
        self._push({"ph": "i", "name": name, "cat": "event", "s": "t",
                    "ts": self._now_us(), "pid": pid, "tid": tid,
                    **({"args": args} if args else {})})

    def counter(self, name: str, value: float, pid: int = 1) -> None:
        if not self.enabled:
            return
        self._push({"ph": "C", "name": name, "cat": "counter",
                    "ts": self._now_us(), "pid": pid, "tid": TICK_TID,
                    "args": {name: round(float(value), 4)}})

    def lifecycle(self, uid: int, state: str, pid: int = 1, **args) -> None:
        """Advance request ``uid``'s lifecycle track: the previous state is
        closed as an "X" span covering its whole duration; terminal states
        and one-off edges (``preempt``) additionally land as instants."""
        if not self.enabled:
            return
        now = self._now_us()
        key = (pid, uid)
        self._name_tid(pid, uid, f"req-{uid}")
        prev = self._open_life.pop(key, None)
        if prev is not None:
            pstate, pt0 = prev
            self._push({"ph": "X", "name": pstate, "cat": "request",
                        "ts": pt0, "dur": max(now - pt0, 0.0),
                        "pid": pid, "tid": uid})
        if state in TERMINAL_STATES or state == "preempt":
            self._push({"ph": "i", "name": state, "cat": "request", "s": "t",
                        "ts": now, "pid": pid, "tid": uid,
                        **({"args": args} if args else {})})
            if state == "preempt":         # preempted → back in the queue
                self._open_life[key] = ("queued", now)
        else:
            self._open_life[key] = (state, now)

    # -- export -------------------------------------------------------------
    def to_events(self) -> List[Dict[str, Any]]:
        """Metadata + recorded events + auto-closed open lifecycle spans,
        sorted by timestamp (metadata first) — a self-contained snapshot."""
        now = self._now_us()
        tail = [{"ph": "X", "name": state, "cat": "request", "ts": t0,
                 "dur": max(now - t0, 0.0), "pid": pid, "tid": uid}
                for (pid, uid), (state, t0) in self._open_life.items()]
        body = sorted(list(self.events) + tail, key=lambda e: e["ts"])
        return list(self._meta) + body

    def dumps_jsonl(self) -> str:
        return "\n".join(json.dumps(e, separators=(",", ":"))
                         for e in self.to_events()) + "\n"

    def dump(self, path) -> None:
        """Write the trace: ``*.jsonl`` → strict JSONL (one event per
        line); anything else → ``{"traceEvents": [...]}`` JSON. Both load
        in Perfetto (ui.perfetto.dev)."""
        import os
        text = (self.dumps_jsonl() if str(path).endswith(".jsonl")
                else json.dumps({"traceEvents": self.to_events()}, indent=1))
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, path)


#: Shared disabled tracer — the engine default. Never records anything.
NULL_TRACER = Tracer(enabled=False)


class CompileWatch:
    """Wrap a jitted callable; count compilations and trace their shapes.

    Detection is *shape-signature based and race-free*: each call computes
    its argument shape/dtype signature and atomically tests-and-adds it to
    a lock-protected seen-set — a signature's first caller is the compile,
    every later caller (including a concurrent one on another thread) is a
    cache hit. The earlier implementation compared the underlying jit
    cache size before/after the call, which misattributed compiles under
    threaded dispatch: two threads interleaving calls both observe the
    cache grow by someone else's entry (or neither observes its own). The
    async runtime dispatches from a worker thread while warmup/benches may
    call from the main thread, so the watch must be correct under
    concurrency. On a compile the watch bumps ``compiles``, invokes
    ``on_compile(name, shapes)`` and emits a ``jit_compile`` instant
    naming the shape bucket — shape-bucket churn that stalls a tick shows
    up in the trace exactly where the stall happened.
    """

    def __init__(self, fn: Callable, name: str, tracer: Tracer = NULL_TRACER,
                 on_compile: Optional[Callable[[str, str], None]] = None,
                 pid: int = 1):
        self._fn = fn
        self.name = name
        self.tracer = tracer
        self.on_compile = on_compile
        self.pid = pid
        self.compiles = 0
        #: whether the most recent call grew the jit cache — the engine's
        #: dispatch probe reads this so the profiler can keep compile+trace
        #: wall time out of the per-executable timing mean
        self.last_compiled = False
        self._lock = threading.Lock()
        self._seen_sigs: set = set()
        # (params pytree, its signature) of the last call: the engine hands
        # every step the same params object until an adapter load rebuilds
        # it, so flattening it again each call is wasted host time
        self._params_sig: Optional[tuple] = None

    @staticmethod
    def _shapes(args) -> str:
        try:
            import jax
            leaves = jax.tree_util.tree_leaves(args)
        except Exception:
            leaves = list(args)
        out, seen = [], set()
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            sig = "x".join(map(str, shape)) or "scalar"
            if sig not in seen:
                seen.add(sig)
                out.append(sig)
        return ",".join(out[:8]) or "scalar"

    @staticmethod
    def _sig(args, kwargs) -> str:
        """Compile-cache key approximation: arg shapes + dtypes plus the
        static kwargs (e.g. ``use_topp``/``use_seeds`` flip the compiled
        graph at identical array shapes)."""
        try:
            import jax
            leaves = jax.tree_util.tree_leaves(args)
        except Exception:
            leaves = list(args)
        parts = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is None:
                # python scalars trace as weak-typed constants: the *type*
                # keys the compile cache, the value does not
                parts.append(type(leaf).__name__)
            else:
                parts.append("x".join(map(str, shape))
                             + ":" + str(getattr(leaf, "dtype", "?")))
        if kwargs:
            parts.append(repr(sorted(kwargs.items())))
        return "|".join(parts)

    def _call_sig(self, args, kwargs) -> str:
        """``_sig`` of a call whose leading params pytree (a dict) keeps
        its signature while it is the same object as on the last call."""
        if not args or not isinstance(args[0], dict):
            return self._sig(args, kwargs)
        cached = self._params_sig
        if cached is None or cached[0] is not args[0]:
            cached = self._params_sig = (args[0], self._sig(args[:1], None))
        return cached[1] + "|" + self._sig(args[1:], kwargs)

    def __call__(self, *args, **kwargs):
        sig = self._call_sig(args, kwargs)
        with self._lock:
            compiled = sig not in self._seen_sigs
            self._seen_sigs.add(sig)
            if compiled:
                self.compiles += 1
        # read before the call, which may consume (donate) its arrays
        shapes = self._shapes(args) if compiled else None
        out = self._fn(*args, **kwargs)
        self.last_compiled = compiled
        if compiled:
            if self.on_compile is not None:
                self.on_compile(self.name, shapes)
            self.tracer.instant("jit_compile", pid=self.pid, fn=self.name,
                                shapes=shapes)
        return out


# -- trace validation (tests + the CI smoke step) ---------------------------

def load_trace(path) -> List[Dict[str, Any]]:
    """Parse a dumped trace back to its event list (JSONL or JSON array)."""
    with open(path) as f:
        text = f.read()
    stripped = text.lstrip()
    if stripped.startswith("{") and '"traceEvents"' in stripped[:40]:
        return json.loads(stripped)["traceEvents"]
    return [json.loads(line) for line in text.splitlines() if line.strip()]


def validate_trace(path) -> Dict[str, Any]:
    """Structural validity of a dumped trace; raises AssertionError on the
    first violation, returns summary stats otherwise. Checks: every line
    parses (JSONL), required keys per event, "X" events carry a
    non-negative dur, "B"/"E" pairs match per (pid, tid), and non-metadata
    timestamps are monotonic in file order."""
    events = load_trace(path)
    assert events, f"{path}: empty trace"
    last_ts = None
    open_begins: Dict[tuple, int] = {}
    stats = {"events": 0, "tick_spans": 0, "request_spans": 0,
             "instants": 0, "counters": 0}
    for evt in events:
        ph = evt.get("ph")
        assert ph, f"event missing ph: {evt}"
        if ph == "M":
            continue
        stats["events"] += 1
        for key in ("name", "ts", "pid", "tid"):
            assert key in evt, f"event missing {key}: {evt}"
        ts = evt["ts"]
        assert last_ts is None or ts >= last_ts, \
            f"non-monotonic ts: {ts} after {last_ts}"
        last_ts = ts
        track = (evt["pid"], evt["tid"])
        if ph == "X":
            assert evt.get("dur", -1) >= 0, f"X event without dur: {evt}"
            if evt["name"] == "tick":
                stats["tick_spans"] += 1
            if evt.get("cat") == "request":
                stats["request_spans"] += 1
        elif ph == "B":
            open_begins[track] = open_begins.get(track, 0) + 1
        elif ph == "E":
            assert open_begins.get(track, 0) > 0, f"E without B: {evt}"
            open_begins[track] -= 1
        elif ph == "i":
            stats["instants"] += 1
        elif ph == "C":
            stats["counters"] += 1
    assert not any(open_begins.values()), \
        f"unmatched B events: {open_begins}"
    return stats
