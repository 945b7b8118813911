"""Paged KV pool: allocator invariants + round-trip + attention equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as CA
from repro.serving.paged_kv import PagePool, PagedConfig

jax.config.update("jax_enable_x64", False)


def make_pool(**kw):
    cfg = PagedConfig(n_layers=2, n_kv_heads=2, head_dim=16, page=8,
                      n_pages=16, **kw)
    return PagePool(cfg, max_slots=4), cfg


class TestAllocator:
    def test_reserve_release_roundtrip(self):
        pool, cfg = make_pool()
        assert pool.pages_free == 16
        pool.reserve(0, 20)          # 3 pages of 8
        assert len(pool.tables[0]) == 3 and pool.pages_free == 13
        pool.reserve(0, 24)          # same 3 pages
        assert len(pool.tables[0]) == 3
        pool.release(0)
        assert pool.pages_free == 16

    def test_exhaustion_raises(self):
        pool, cfg = make_pool()
        pool.reserve(0, 16 * 8)
        with pytest.raises(MemoryError):
            pool.reserve(1, 8)

    def test_no_page_shared_between_slots(self):
        pool, _ = make_pool()
        pool.reserve(0, 30)
        pool.reserve(1, 30)
        assert not (set(pool.tables[0]) & set(pool.tables[1]))

    def test_fragmentation_savings(self):
        pool, _ = make_pool()
        s = pool.fragmentation_savings(max_len=64, active_lengths=[8, 16, 8])
        assert 0.7 < s < 0.9  # 4 of 24 reserved pages actually used → 83%


class TestRoundTrip:
    def test_token_write_gather(self):
        pool, cfg = make_pool()
        rng = np.random.default_rng(0)
        toks = [jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32)
                for _ in range(10)]
        for pos, t in enumerate(toks):
            pool.write_token(0, pos, t, t * 2)
        k, v = pool.gather_slot(0)
        assert k.shape == (2, 1, 2, 16, 16)  # 2 pages of 8
        for pos, t in enumerate(toks):
            np.testing.assert_allclose(
                np.asarray(k[:, 0, :, pos], np.float32),
                np.asarray(t.astype(cfg.dtype), np.float32))

    def test_span_write_crosses_pages(self):
        pool, cfg = make_pool()
        rng = np.random.default_rng(1)
        span = jnp.asarray(rng.normal(size=(2, 2, 20, 16)), jnp.float32)
        pool.write_span(1, 0, span, span)
        k, _ = pool.gather_slot(1)
        np.testing.assert_allclose(
            np.asarray(k[:, 0, :, :20], np.float32),
            np.asarray(span.astype(cfg.dtype), np.float32))

    def test_span_write_unaligned_start_crosses_boundary(self):
        """A span starting mid-page and ending mid-page two pages later must
        land token-exact (the per-page loop splits at both boundaries)."""
        pool, cfg = make_pool()
        rng = np.random.default_rng(3)
        head = jnp.asarray(rng.normal(size=(2, 2, 5, 16)), jnp.float32)
        span = jnp.asarray(rng.normal(size=(2, 2, 14, 16)), jnp.float32)
        pool.write_span(0, 0, head, head)          # positions 0..4
        pool.write_span(0, 5, span, span * 3)      # positions 5..18: 3 pages
        assert len(pool.tables[0]) == 3 and int(pool.lengths[0]) == 19
        k, v = pool.gather_slot(0)
        np.testing.assert_allclose(
            np.asarray(k[:, 0, :, 5:19], np.float32),
            np.asarray(span.astype(cfg.dtype), np.float32))
        np.testing.assert_allclose(
            np.asarray(v[:, 0, :, 5:19], np.float32),
            np.asarray((span * 3).astype(cfg.dtype), np.float32))
        # the head must survive the second write untouched
        np.testing.assert_allclose(
            np.asarray(k[:, 0, :, :5], np.float32),
            np.asarray(head.astype(cfg.dtype), np.float32))


class TestBatchedOps:
    def test_batch_tables_pads_with_scratch(self):
        pool, cfg = make_pool()
        pool.reserve(0, 20)          # 3 pages
        pool.reserve(2, 5)           # 1 page
        t = pool.batch_tables([0, 2], n_pages=4, batch=4)
        assert t.shape == (4, 4)
        assert list(t[0, :3]) == pool.tables[0] and t[0, 3] == pool.scratch_page
        assert t[2, 0] == pool.tables[2][0]
        assert (t[1] == pool.scratch_page).all()  # inactive row

    def test_write_tokens_gather_batch_roundtrip(self):
        pool, cfg = make_pool()
        rng = np.random.default_rng(4)
        pool.reserve(0, 10)
        pool.reserve(1, 3)
        for pos0, pos1 in [(0, 0), (1, 1), (9, 2)]:
            toks = jnp.asarray(rng.normal(size=(2, 4, 2, 16)), jnp.float32)
            page_ids = np.asarray(
                [pool.tables[0][pos0 // cfg.page], pool.tables[1][pos1 // cfg.page],
                 pool.scratch_page, pool.scratch_page], np.int32)
            offs = np.asarray([pos0 % cfg.page, pos1 % cfg.page, 0, 0], np.int32)
            pool.write_tokens(page_ids, offs, toks, toks * 2)
        tables = pool.batch_tables([0, 1], n_pages=2, batch=4)
        k, v = pool.gather_batch(tables)
        assert k.shape == (2, 4, 2, 2 * cfg.page, 16)
        # last written token of slot 0 (pos 9) and slot 1 (pos 2)
        np.testing.assert_allclose(np.asarray(k[:, 0, :, 9], np.float32),
                                   np.asarray(toks[:, 0].astype(cfg.dtype),
                                              np.float32))
        np.testing.assert_allclose(np.asarray(v[:, 1, :, 2], np.float32),
                                   np.asarray((toks[:, 1] * 2).astype(cfg.dtype),
                                              np.float32))

    def test_scratch_page_never_allocated(self):
        pool, cfg = make_pool()
        pool.reserve(0, cfg.n_pages * cfg.page)   # drain the whole pool
        assert pool.scratch_page not in pool.tables[0]

    def test_release_keep_skips_cache_owned_pages(self):
        pool, cfg = make_pool()
        pool.reserve(0, 24)                       # 3 pages
        cached = pool.tables[0][:2]
        pool.release(0, keep=2)
        assert pool.pages_free == cfg.n_pages - 2
        assert not (set(cached) & set(pool.free))
        pool.free_pages(cached)                   # cache eviction path
        assert pool.pages_free == cfg.n_pages


class TestPagedFlashDecode:
    """Block tables threaded into the Pallas kernel's page-shaped context
    loop (scalar prefetch) == contiguous-gather oracle."""

    def _case(self, seed, dtype):
        from repro.kernels.flash_decode.ops import paged_decode_attention
        from repro.kernels.flash_decode.paged import paged_flash_decode_ref
        rng = np.random.default_rng(seed)
        b, hq, hkv, d, page, n_pages, n_p = 3, 8, 2, 32, 16, 10, 4
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(n_pages + 1, hkv, page, d)),
                         jnp.float32).astype(dtype)
        vp = jnp.asarray(rng.normal(size=(n_pages + 1, hkv, page, d)),
                         jnp.float32).astype(dtype)
        tables = jnp.asarray(rng.integers(0, n_pages, size=(b, n_p)), jnp.int32)
        lengths = jnp.asarray([page * n_p, 17, 1], jnp.int32)
        out = paged_decode_attention(q, kp[None], vp[None], tables, lengths,
                                     1.0, layer=jnp.int32(0), use_kernel=True,
                                     interpret=True)
        ref = paged_flash_decode_ref(
            q.reshape(b, hkv, hq // hkv, d), kp.astype(jnp.float32),
            vp.astype(jnp.float32), tables, lengths, 1.0
        ).reshape(b, hq, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_matches_gather_oracle_f32(self):
        self._case(0, jnp.float32)

    def test_kernel_matches_gather_oracle_fp8(self):
        self._case(1, jnp.float8_e4m3fn)

    def test_kernel_matches_engine_view_path(self):
        """The kernel over a live PagePool == attention over gather_batch's
        contiguous view (the engine's pure-JAX decode path)."""
        from repro.core import attention as CA
        from repro.kernels.flash_decode.ops import paged_decode_attention
        pool, cfg = make_pool(dtype=jnp.float32)
        rng = np.random.default_rng(5)
        n_tok = 19
        ks = jnp.asarray(rng.normal(size=(2, 2, n_tok, 16)), jnp.float32)
        vs = jnp.asarray(rng.normal(size=(2, 2, n_tok, 16)), jnp.float32)
        pool.write_span(0, 0, ks, vs)
        tables = pool.batch_tables([0], n_pages=3, batch=1)
        kb, vb = pool.gather_batch(tables)          # (L, 1, H, 24, D)
        q = jnp.asarray(rng.normal(size=(1, 2, 16)), jnp.float32)
        out_kernel = paged_decode_attention(
            q, pool.k, pool.v, jnp.asarray(tables),
            jnp.asarray([n_tok], jnp.int32), 1.0, layer=jnp.int32(0),
            use_kernel=True, interpret=True)
        mask = (jnp.arange(kb.shape[3]) < n_tok)[None]
        out_view = CA.dense_decode_attention(q, kb[0], vb[0], mask=mask)
        np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_view),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float8_e4m3fn])
    def test_layer_index_equals_layer_slice(self, dtype):
        """The kernel reading layer ``l`` of the whole (L, ...) pool through
        its scalar-prefetched layer index is, bit for bit, the kernel over
        that layer alone (a one-layer pool, layer 0): slots on their first
        page, on their second, across a whole table, and an inactive row
        whose padded table points only at the scratch page."""
        from repro.kernels.flash_decode.ops import paged_decode_attention
        rng = np.random.default_rng(7)
        n_layers, hq, hkv, d, page, n_pages = 3, 8, 2, 32, 16, 6
        scratch = n_pages
        shape = (n_layers, n_pages + 1, hkv, page, d)
        kp = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)
        vp = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)
        tables = jnp.asarray([[0, scratch, scratch], [1, 2, scratch],
                              [3, 4, 5], [scratch] * 3], jnp.int32)
        lengths = jnp.asarray([5, page + 3, 3 * page, 0], jnp.int32)
        q = jnp.asarray(rng.normal(size=(4, hq, d)), jnp.float32)
        outs = []
        for layer in range(n_layers):
            whole = paged_decode_attention(
                q, kp, vp, tables, lengths, 1.0, layer=jnp.int32(layer),
                use_kernel=True, interpret=True)
            alone = paged_decode_attention(
                q, kp[layer][None], vp[layer][None], tables, lengths, 1.0,
                layer=jnp.int32(0), use_kernel=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(whole),
                                          np.asarray(alone))
            outs.append(np.asarray(whole))
        # the index selects: the layers' outputs differ
        assert not np.array_equal(outs[0], outs[1])

    @pytest.mark.parametrize("layer", [0, 2])
    def test_kv_append_writes_what_a_scatter_writes(self, layer):
        """`paged_kv_append` leaves the pools exactly as an XLA scatter of
        each slot's row would: rows at the start, middle and end of a page
        and of its 8-row tile groups, on first and later pages; inactive
        slots (all on the scratch page) write garbage only there."""
        from repro.kernels.flash_decode.paged import paged_kv_append
        rng = np.random.default_rng(11)
        n_layers, hkv, d, page, n_pages = 3, 2, 32, 16, 6
        scratch = n_pages
        shape = (n_layers, n_pages + 1, hkv, page, d)
        f8 = jnp.float8_e4m3fn
        kp = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(f8)
        vp = jnp.asarray(rng.normal(size=shape), jnp.float32).astype(f8)
        pages = jnp.asarray([0, 2, 5, 3, scratch, scratch], jnp.int32)
        offs = jnp.asarray([0, 7, 15, 9, 3, 8], jnp.int32)
        kn = jnp.asarray(rng.normal(size=(6, hkv, d)), jnp.float32).astype(f8)
        vn = jnp.asarray(rng.normal(size=(6, hkv, d)), jnp.float32).astype(f8)
        got = paged_kv_append(kp, vp, kn, vn, jnp.int32(layer), pages, offs,
                              interpret=True)
        want = (kp.at[layer, pages, :, offs].set(kn),
                vp.at[layer, pages, :, offs].set(vn))
        bits = lambda x: np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint8))
        for g, w, before in zip(got, want, (kp, vp)):
            g, w, before = bits(g), bits(w), bits(before)
            np.testing.assert_array_equal(g[:, :scratch], w[:, :scratch])
            # every other layer, and the live pages' other rows, untouched
            assert (g[:, :scratch] != before[:, :scratch]).any()
            np.testing.assert_array_equal(
                np.delete(g, layer, axis=0), np.delete(before, layer, axis=0))

    def test_attention_over_paged_equals_contiguous(self):
        """Decode attention on a gathered paged cache == on the flat cache."""
        pool, cfg = make_pool()
        rng = np.random.default_rng(2)
        s_used = 19
        ks = jnp.asarray(rng.normal(size=(2, 2, s_used, 16)), jnp.float32)
        vs = jnp.asarray(rng.normal(size=(2, 2, s_used, 16)), jnp.float32)
        pool.write_span(2, 0, ks, vs)
        kp, vp = pool.gather_slot(2)

        q = jnp.asarray(rng.normal(size=(1, 2, 16)), jnp.float32)
        # layer 0, mask padded tail beyond s_used
        s_total = kp.shape[3]
        mask = (jnp.arange(s_total) < s_used)[None]
        out_paged = CA.dense_decode_attention(
            q, kp[0].astype(jnp.float32), vp[0].astype(jnp.float32), mask=mask)
        out_flat = CA.dense_decode_attention(
            q, ks[0:1].astype(cfg.dtype).astype(jnp.float32)[None][0],
            vs[0:1].astype(cfg.dtype).astype(jnp.float32)[None][0])
        np.testing.assert_allclose(np.asarray(out_paged), np.asarray(out_flat),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The decode step's kernel path: whole pool carried through the layer scan
# ---------------------------------------------------------------------------


def _moe_prefix_config():
    """A small GQA MoE whose first layer is dense: the model keeps it out
    of the layer scan as an unstacked prefix layer (``kd = 1``)."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.launch.train import reduce_config
    cfg = reduce_config(get_config("arctic-480b"), "tiny")
    return dataclasses.replace(
        cfg, num_layers=3, d_model=256, num_heads=4, num_kv_heads=2,
        head_dim=64, d_ff=256,
        moe=dataclasses.replace(cfg.moe, num_experts=4, expert_d_ff=64,
                                dense_residual_d_ff=128, first_k_dense=1,
                                dense_d_ff=128))


def _bitnet_config():
    from repro.configs.base import get_config
    from repro.launch.train import reduce_config
    return reduce_config(get_config("bitnet-2b"), "tiny")


class TestPagedDecodeKernelPath:
    """``Model._paged_decode_kernel`` (interpret mode) against the XLA
    gather path on a live engine state: the same new rows in the same
    pages, the same logits."""

    @pytest.mark.parametrize("make_cfg", [_bitnet_config, _moe_prefix_config],
                             ids=["scan-only", "dense-prefix-layer"])
    def test_kernel_path_matches_gather_path(self, make_cfg):
        import dataclasses
        from repro.models.transformer import Model
        from repro.serving import PagedKV, RequestSpec, ServeEngine
        model = Model(make_cfg(), mode="serve")
        params = model.init(jax.random.PRNGKey(0))
        assert len(params.get("prefix", [])) == (
            1 if model.cfg.moe is not None else 0)
        eng = ServeEngine(model, params, max_slots=2, max_len=32,
                          kv=PagedKV(page=8))
        req = eng.submit(list(range(5, 15)), RequestSpec(max_new_tokens=6))
        for _ in range(12):
            eng.tick()
        # slot 0 decodes on its second page; slot 1 is idle (scratch page)
        assert req.state == "running" and eng.pos[0] > 8
        state = eng.kv.decode_state([0], eng.pos)
        tokens = jnp.asarray(np.asarray([req.output[-1], 0], np.int32))
        pos = jnp.asarray(eng.pos)
        lg, new_g = model.decode_step(params, state, tokens, pos)
        kernel = dataclasses.replace(model, paged_attn="kernel")
        lk, new_k = kernel.decode_step(params, state, tokens, pos)
        np.testing.assert_allclose(np.asarray(lk)[0], np.asarray(lg)[0],
                                   rtol=2e-4, atol=2e-4)
        scratch = eng.pool.scratch_page
        bits = lambda x: np.asarray(jax.lax.bitcast_convert_type(
            x, jnp.uint8))
        for g, k, old in ((new_g.k_pool, new_k.k_pool, state.k_pool),
                          (new_g.v_pool, new_k.v_pool, state.v_pool)):
            g, k, old = bits(g), bits(k), bits(old)
            np.testing.assert_array_equal(np.delete(k, scratch, axis=1),
                                          np.delete(g, scratch, axis=1))
            # every layer, the prefix one included, wrote its row
            changed = (k != old).any(axis=(1, 2, 3, 4))
            assert changed.all(), changed

    def test_kernel_path_on_two_lanes_equals_one_device(self):
        """A pool on a (data=1, model=2) mesh (`serve.py --tp 2`) runs the
        kernels per lane, and the step gives the one-device step's logits
        and pools bit for bit: pages split over the lanes (10 pages) and a
        replicated pool (11 pages, which 2 does not divide). Slots on their
        second and third page, and an idle slot on the scratch page."""
        import os
        import pathlib
        import subprocess
        import sys
        import textwrap
        script = textwrap.dedent("""
            import dataclasses
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs.base import get_config
            from repro.launch.train import reduce_config
            from repro.models.attention import PagedKVState
            from repro.models.sharding import paged_pool_spec
            from repro.models.transformer import Model
            from repro.serving.sharded import replica_meshes

            cfg = reduce_config(get_config("bitnet-2b"), "tiny")
            model = Model(cfg, mode="serve", paged_attn="kernel")
            params = model.init(jax.random.PRNGKey(0))
            mesh = replica_meshes(1, tp=2)[0]
            rep = NamedSharding(mesh, P())
            step = jax.jit(model.decode_step)
            bits = lambda x: np.asarray(
                jax.lax.bitcast_convert_type(x, jnp.uint8))
            rng = np.random.default_rng(3)
            page = 8
            for n_pages, split in ((10, "model"), (11, None)):
                scratch = n_pages - 1
                shape = (cfg.num_layers, n_pages, cfg.num_kv_heads, page,
                         cfg.head_dim)
                kp, vp = (jnp.asarray(rng.normal(size=shape),
                                      jnp.float32).astype(jnp.float8_e4m3fn)
                          for _ in range(2))
                lengths = jnp.asarray([page + 2, 2 * page + 5, 0], jnp.int32)
                state = PagedKVState(
                    kp, vp,
                    jnp.asarray([[0, 1, scratch], [2, 3, 4], [scratch] * 3],
                                jnp.int32),
                    jnp.asarray([1, 4, scratch], jnp.int32),
                    jnp.asarray([1, 4, 0], jnp.int32), lengths)
                args = (params, state, jnp.asarray([5, 9, 0], jnp.int32),
                        lengths - 1)
                one_lg, one = step(*args)

                # the pool as `shard_engine` places it
                spec = paged_pool_spec(shape, mesh)
                assert spec[1] == split, spec
                sh = NamedSharding(mesh, spec)
                p2, s2, t2, pos2 = jax.device_put(args, rep)
                s2 = dataclasses.replace(s2, k_pool=jax.device_put(kp, sh),
                                         v_pool=jax.device_put(vp, sh))
                two_lg, two = step(p2, s2, t2, pos2)
                assert two.k_pool.sharding.is_equivalent_to(sh, len(shape))
                np.testing.assert_array_equal(np.asarray(one_lg),
                                              np.asarray(two_lg))
                for a, b_, old in ((one.k_pool, two.k_pool, kp),
                                   (one.v_pool, two.v_pool, vp)):
                    np.testing.assert_array_equal(bits(a), bits(b_))
                    # every layer wrote its rows
                    assert (bits(a) != bits(old)).any(axis=(1, 2, 3, 4)).all()
            print("LANES-OK")
        """)
        res = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=600, env={**os.environ, "PYTHONPATH": "src"},
            cwd=str(pathlib.Path(__file__).resolve().parents[1]))
        assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-3000:])
        assert "LANES-OK" in res.stdout
