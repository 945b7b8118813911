"""The serving path's own names in a ``jax.profiler`` trace.

Model scopes (``repro.obs.names``) must tag the device ops of the compiled
decode step and prefill while leaving the computation exactly as it was;
the engine's tick phases open ``serve.<phase>`` spans on the profiler's
clock without changing the self-time accounting; ``decode_steps`` counts
decode-executable dispatches only; and ``CompileWatch`` flattens an
unchanged params pytree once, not on every call.
"""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import names
from repro.serving.obs.tracer import CompileWatch

MODEL_SCOPES = ("ternary_proj", "attn", "kv_append", "lm_head", "lora")


@pytest.fixture(scope="module")
def engine():
    from repro.launch.serve import build_engine
    return build_engine("bitnet-2b", "tiny", slots=2, max_len=64,
                        prefill="batched", kv="paged", page=8, n_adapters=2)


def _lower_decode(eng, attn):
    eng.model.paged_attn = attn
    try:
        state = eng.kv.decode_state([0, 1], eng.pos)
        return jax.jit(eng._decode_fn).lower(
            eng._effective_params(), state, jnp.zeros((2,), jnp.int32),
            jnp.asarray(eng.pos.copy()), jnp.asarray([1, 0], jnp.int32))
    finally:
        eng.model.paged_attn = "auto"


def _lower_prefill(eng):
    from repro.serving.engine import _fresh_prefill
    # a jit of its own: the engine's prefill jit would hand back its
    # cached trace
    fresh = jax.jit(functools.partial(_fresh_prefill, eng.model),
                    static_argnums=(2,))
    return fresh.lower(eng._effective_params(), jnp.zeros((1, 16), jnp.int32),
                       eng.max_len, jnp.asarray([1], jnp.int32))


def _has_scope(text, scope):
    """Whether a location in the lowering names ``scope`` as a path part
    (scan bodies carry paths relative to the scan)."""
    return re.search(rf'["/]{scope}/', text) is not None


LOWERINGS = {"decode-gather": lambda e: _lower_decode(e, "gather"),
             "decode-kernel": lambda e: _lower_decode(e, "kernel"),
             "prefill": _lower_prefill}


@pytest.mark.parametrize("which", sorted(LOWERINGS))
def test_model_scopes_tag_the_compiled_step(engine, which):
    text = LOWERINGS[which](engine).as_text(debug_info=True)
    for scope in MODEL_SCOPES + (names.EMBED,):
        assert _has_scope(text, scope), scope


@pytest.mark.parametrize("which", sorted(LOWERINGS))
def test_model_scopes_change_no_computation(engine, which, monkeypatch):
    scoped = LOWERINGS[which](engine).as_text()
    monkeypatch.setattr(names, "scope", lambda name: contextlib.nullcontext())
    plain = LOWERINGS[which](engine)
    assert not _has_scope(plain.as_text(debug_info=True), "ternary_proj")
    assert plain.as_text() == scoped


def test_head_ops_are_named_by_the_head_not_the_projection():
    from repro.models import layers
    from repro.core import ternary
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)), jnp.float32)
    t, s = ternary.quantize(w)
    head = {"packed": ternary.pack2(t), "scale": s}
    text = jax.jit(lambda x: layers.lm_head_logits(head, x, "serve")).lower(
        jnp.ones((2, 8), jnp.bfloat16)).as_text(debug_info=True)
    assert _has_scope(text, "lm_head") and "ternary_proj" not in text


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_phases_open_serve_spans_and_keep_self_time(engine, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    before = dict(engine.stats.phase_ms)
    with engine._phase("schedule"):
        with engine._phase("admit"):
            pass
    assert _Recorder.log == [("enter", "serve.schedule"),
                             ("enter", "serve.admit"),
                             ("exit", "serve.admit"),
                             ("exit", "serve.schedule")]
    pm = engine.stats.phase_ms
    assert pm["admit"] > before.get("admit", 0.0)
    assert pm["schedule"] >= before.get("schedule", 0.0)
    with pytest.raises(KeyError):
        engine._phase("not-a-phase").__enter__()


def test_every_phase_name_is_listed_once():
    assert len(set(names.PHASES)) == len(names.PHASES)
    assert all(v == "serve." + k for k, v in names.SERVE_SPANS.items())
    assert all(v.startswith("backlog.") for v in names.BACKLOG_SPANS.values())
    assert len(set(names.SCOPES)) == len(names.SCOPES)


def test_decode_steps_count_only_decode_dispatches(engine):
    from repro.serving import PagedKV, RequestSpec, ServeEngine
    eng = ServeEngine(engine.model, engine.params, max_slots=2, max_len=64,
                      prefill="batched", prefill_chunk=4,
                      kv=PagedKV(page=8, n_pages=24))
    calls = []
    decode = eng._decode

    def counted(*a, **kw):
        calls.append(1)
        return decode(*a, **kw)

    eng._decode = counted
    eng.submit(list(range(1, 14)), RequestSpec(max_new_tokens=3))
    eng.run_until_drained()
    assert eng.stats.decode_steps == len(calls) > 0
    # the 13-token prompt streams in 4-token chunks: prefill-only ticks
    assert eng.stats.ticks > eng.stats.decode_steps


def test_compile_watch_flattens_unchanged_params_once(monkeypatch):
    seen = []
    sig = CompileWatch._sig

    def counting(args, kwargs):
        seen.append("params" if isinstance(args[0], dict) else "rest")
        return sig(args, kwargs)

    monkeypatch.setattr(CompileWatch, "_sig", staticmethod(counting))
    w = CompileWatch(jax.jit(lambda p, x: p["w"] * x), "mul")
    p4 = {"w": jnp.ones((4,))}
    x4 = jnp.ones((4,))
    for _ in range(3):
        w(p4, x4)
    # params flattened once; the rest of the call every time
    assert seen == ["params"] + ["rest"] * 3 and w.compiles == 1
    w({"w": jnp.ones((4,))}, x4)          # a new object, same shapes
    assert seen.count("params") == 2 and w.compiles == 1
    w({"w": jnp.ones((8,))}, jnp.ones((8,)))
    assert w.compiles == 2
    # a non-dict leading argument keeps the plain signature
    v = CompileWatch(jax.jit(lambda x: x + 1), "inc")
    v(x4)
    v(jnp.ones((8,)))
    assert v.compiles == 2
