"""The names the serving path gives its own work in a ``jax.profiler`` trace.

Two kinds, both listed here once:

  * **model scopes** — ``jax.named_scope`` names on the device ops of the
    compiled model step. A scope only changes op metadata (the ``tf_op`` /
    ``op_name`` a trace shows for each device op), never the computation.
    Where scopes nest, the innermost one names the op.
  * **host spans** — ``jax.profiler.TraceAnnotation`` names on the profiler's
    own clock. The serving engine's dispatch thread opens ``serve.<phase>``
    around each tick phase, the runtime's backlog thread ``backlog.<event>``
    around each event it replays, and a submitting client ``client.bind``
    while it waits for the dispatch thread to take its request. The prefix
    tells the threads apart in a trace.

Every span name is built here, at import, so no call builds a string.
"""
from __future__ import annotations

import jax

# -- model scopes --------------------------------------------------------------

#: serve-mode 2-bit unpack + matmul of a ternary projection
TERNARY_PROJ = "ternary_proj"
#: the per-tenant multi-tenant LoRA contribution
LORA = "lora"
#: decode / prefill attention over the KV cache (kernel or gather path)
ATTN = "attn"
#: the new tokens' K/V written into the cache or page pool
KV_APPEND = "kv_append"
#: the vocabulary head (tied or untied)
LM_HEAD = "lm_head"
#: the token embedding lookup
EMBED = "embed"

SCOPES = (TERNARY_PROJ, LORA, ATTN, KV_APPEND, LM_HEAD, EMBED)


def scope(name: str):
    """Context manager naming the device ops traced inside it."""
    return jax.named_scope(name)


# -- host spans ------------------------------------------------------------------

#: engine tick phases, timed by ``ServeEngine._phase`` into
#: ``EngineStats.phase_ms`` and opened as ``serve.<phase>`` spans
PHASES = ("schedule", "admit", "prefill", "prefill_chunk", "kv_write",
          "decode", "spec_verify", "commit", "sample", "emit",
          "wait_device", "inbox", "idle")
SERVE_SPANS = {p: "serve." + p for p in PHASES}

#: events the async runtime's backlog thread replays
BACKLOG_EVENTS = ("token", "done", "submit", "admit", "preempt", "expire",
                  "cancel", "tick", "barrier")
BACKLOG_SPANS = {k: "backlog." + k for k in BACKLOG_EVENTS}

#: a submitting client's wait for the dispatch thread to bind its request
CLIENT_BIND = "client.bind"
