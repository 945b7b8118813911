"""C3 equivalence: TOM two-phase decode == stock flash-decode == dense oracle,
single-device and under shard_map over a context-sharded lane axis."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                      # real hypothesis when installed (CI does)
    from hypothesis import given, settings
    import hypothesis.strategies as st
except ImportError:       # deterministic fallback — properties never skip
    from repro.testing.hypothesis_compat import given, settings, st  # noqa: E402

from repro.core import attention as CA

jax.config.update("jax_enable_x64", False)


def _qkv(seed, b=2, h=4, s=128, d=32):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(b, h, s, d)), jnp.float32)
    return q, k, v


class TestSingleDeviceEquivalence:
    def test_tom_equals_dense(self):
        q, k, v = _qkv(0)
        ref = CA.dense_decode_attention(q, k, v)
        out = CA.tom_flash_decode(q, k, v, axis_name=None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_stock_equals_dense(self):
        q, k, v = _qkv(1)
        ref = CA.dense_decode_attention(q, k, v)
        out = CA.stock_flash_decode(q, k, v, axis_name=None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_chunked_equals_dense(self):
        q, k, v = _qkv(2, s=96)
        ref = CA.dense_decode_attention(q, k, v)
        out = CA.chunked_flash_decode(q, k, v, chunk=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @settings(deadline=None, max_examples=10)
    @given(seed=st.integers(0, 1000))
    def test_property_tom_vs_stock(self, seed):
        q, k, v = _qkv(seed, b=1, h=2, s=64, d=16)
        a = CA.tom_flash_decode(q, k, v, axis_name=None)
        b = CA.stock_flash_decode(q, k, v, axis_name=None)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_masked(self):
        q, k, v = _qkv(3)
        mask = jnp.arange(128)[None, :] < 77
        mask = jnp.broadcast_to(mask, (2, 128))
        ref = CA.dense_decode_attention(q, k, v, mask=mask)
        out = CA.tom_flash_decode(q, k, v, axis_name=None, mask_local=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gqa_wrapper(self):
        r = np.random.default_rng(4)
        q = jnp.asarray(r.normal(size=(2, 8, 16)), jnp.float32)   # Hq=8
        k = jnp.asarray(r.normal(size=(2, 2, 64, 16)), jnp.float32)  # Hkv=2
        v = jnp.asarray(r.normal(size=(2, 2, 64, 16)), jnp.float32)
        out = CA.gqa_decode(q, k, v, axis_name=None, variant="tom")
        # oracle: expand kv heads
        ke = jnp.repeat(k, 4, axis=1)
        ve = jnp.repeat(v, 4, axis=1)
        ref = CA.dense_decode_attention(q, ke, ve)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


_SHARDMAP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import attention as CA

    mesh = jax.make_mesh((8,), ("model",))
    r = np.random.default_rng(0)
    b, h, s, d = 2, 4, 128, 32
    q = jnp.asarray(r.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(r.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(r.normal(size=(b, h, s, d)), jnp.float32)
    ref = CA.dense_decode_attention(q, k, v)

    for variant, fn in (("tom", CA.tom_flash_decode),
                        ("stock", CA.stock_flash_decode)):
        sharded = jax.shard_map(
            partial(fn, axis_name="model"),
            mesh=mesh,
            in_specs=(P(), P(None, None, "model", None), P(None, None, "model", None)),
            out_specs=P(),
        )
        out = jax.jit(sharded)(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print(variant, "OK")
""")


class TestShardMapLanes:
    @pytest.mark.slow
    def test_two_phase_over_8_lanes(self):
        """The paper's dataflow with the KV cache context-sharded across 8
        lanes; the reduction tree is psum/pmax. Runs in a subprocess so the
        8-device XLA flag doesn't leak into this process."""
        res = subprocess.run(
            [sys.executable, "-c", _SHARDMAP_SCRIPT],
            capture_output=True, text=True, timeout=600,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "HOME": "/root"},
            cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]))
        assert res.returncode == 0, res.stderr[-2000:]
        assert "tom OK" in res.stdout and "stock OK" in res.stdout
