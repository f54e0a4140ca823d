"""Tests for TP / SP (ring + Ulysses) / PP / EP / hierarchical mesh over
the 8-device virtual CPU mesh. Every scheme is checked against a dense
single-device reference computation — the sharded result must match the
unsharded math, not merely run."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu.parallel as par
from horovod_tpu.ops.attention import dot_product_attention, flash_attention


def _mesh(axes):
    n = math.prod(abs(s) for s in axes.values())
    return par.make_mesh(axes, devices=jax.devices()[:n])


class TestMesh:
    def test_make_mesh_shapes(self, hvd):
        m = par.make_mesh({"dp": 2, "tp": 4})
        assert m.shape == {"dp": 2, "tp": 4}

    def test_make_mesh_wildcard(self, hvd):
        m = par.make_mesh({"dp": 2, "tp": -1})
        assert m.shape["tp"] == 4

    def test_make_mesh_bad_product(self, hvd):
        from horovod_tpu.common.exceptions import InvalidArgumentError

        with pytest.raises(InvalidArgumentError):
            par.make_mesh({"dp": 3, "tp": 3})

    def test_hierarchical_mesh(self, hvd):
        m = par.hierarchical_mesh(inner=4)
        assert m.shape == {"dcn": 2, "ici": 4}

    def test_hierarchical_allreduce_matches_flat(self, hvd):
        m = par.hierarchical_mesh(inner=4)
        x = jnp.arange(2 * 13, dtype=jnp.float32).reshape(2, 13)

        def fn(x):
            return par.hierarchical_allreduce(x, "dcn", "ici")

        # Grouped-psum replication the vma checker cannot infer
        # (lax.pcast to='invariant' is not implemented); scoped opt-out.
        out = jax.jit(jax.shard_map(fn, mesh=m, in_specs=P(),
                                    out_specs=P(), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 8,
                                   rtol=1e-6)

    def test_hierarchical_allreduce_average(self, hvd):
        m = par.hierarchical_mesh(inner=2)
        x = jnp.ones((5,), jnp.float32)
        out = jax.jit(jax.shard_map(
            lambda t: par.hierarchical_allreduce(t, average=True),
            mesh=m, in_specs=P(), out_specs=P(), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out), np.ones(5), rtol=1e-6)


class TestHierarchicalKnobs:
    """HOROVOD_HIERARCHICAL_ALLREDUCE/ALLGATHER change the executed
    collective in the flagship SPMD lane (round-1 gap: parsed, never
    consulted). Reference semantics: operations.cc:1284-1436, :929-1032."""

    @pytest.fixture()
    def hier_config(self, hvd):
        from horovod_tpu.common.state import global_state

        cfg = global_state().config
        saved = (cfg.hierarchical_allreduce, cfg.hierarchical_allgather,
                 cfg.hierarchical_inner_size)
        cfg.hierarchical_allreduce = True
        cfg.hierarchical_allgather = True
        cfg.hierarchical_inner_size = 4  # 8 chips = 2 (dcn) x 4 (ici)
        yield cfg
        (cfg.hierarchical_allreduce, cfg.hierarchical_allgather,
         cfg.hierarchical_inner_size) = saved

    def test_fused_reduce_hierarchical_matches_flat(self, hvd, hier_config):
        from horovod_tpu.jax.fusion import fused_reduce

        def fn(x, y):
            a, b = fused_reduce([x, y], average=False)
            return a, b

        x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)
        y = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3) * 0.5
        a, b = hvd.spmd_run(fn, x, y, in_specs=(P("hvd"), P("hvd")),
                            out_specs=(P(), P()))
        # Sum over the 8 rank-shards of each tensor.
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(x).reshape(8, 1, 6).sum(0), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(y).reshape(8, 1, 3).sum(0), rtol=1e-6)

    def test_knob_changes_lowered_collective(self, hvd, hier_config):
        """The knob must change the program XLA sees: the hierarchical
        ladder lowers to grouped reduce-scatter + two collectives, the
        flat path to one ungrouped all-reduce."""
        from horovod_tpu.common.state import global_state
        from horovod_tpu.jax.fusion import fused_reduce

        def fn(x):
            return fused_reduce([x], average=False)[0]

        x = jnp.ones((8, 16), jnp.float32)
        run = hvd.spmd_fn(fn, in_specs=P("hvd"), out_specs=P())
        hier_text = run._compiled.lower(x).as_text()
        assert "reduce_scatter" in hier_text, hier_text[-2000:]

        global_state().config.hierarchical_allreduce = False

        def fn2(x):
            return fused_reduce([x], average=False)[0]

        flat_text = hvd.spmd_fn(
            fn2, in_specs=P("hvd"), out_specs=P())._compiled.lower(x).as_text()
        assert "reduce_scatter" not in flat_text

    def test_hierarchical_allgather_matches_flat(self, hvd, hier_config):
        from horovod_tpu.common.state import global_state

        def fn(x):
            return hvd.allgather(x)

        x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
        hier = hvd.spmd_run(fn, x, in_specs=P("hvd"), out_specs=P())
        hier_text = hvd.spmd_fn(
            fn, in_specs=P("hvd"), out_specs=P())._compiled.lower(x).as_text()
        # Two-phase = two grouped all-gathers.
        assert hier_text.count("all_gather") >= 2, hier_text[-2000:]

        global_state().config.hierarchical_allgather = False

        def fn2(x):
            return hvd.allgather(x)

        flat = hvd.spmd_run(fn2, x, in_specs=P("hvd"), out_specs=P())
        np.testing.assert_array_equal(np.asarray(hier), np.asarray(flat))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        key = jax.random.PRNGKey(0)
        B, L, H, D = 2, 64, 2, 8
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))
        ref = dot_product_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_causal_block_q_not_multiple_of_block_k(self):
        """Regression: the causal loop bound must cover key blocks partially
        reached by a q-block when block_q % block_k != 0."""
        key = jax.random.PRNGKey(9)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 48, 1, 8)) for i in range(3))
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=24)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_rectangular_blocks(self):
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (1, 32, 1, 4))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 1, 4))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 1, 4))
        ref = dot_product_attention(q, k, v)
        out = flash_attention(q, k, v, block_q=8, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    # Both backward implementations must be exact: the "auto" dispatch
    # routes small test shapes to the scan path, so every gradient test
    # pins the Pallas kernel split explicitly too (review r5: without
    # this, the ~200-line kernel backward had zero CI coverage).
    @pytest.mark.parametrize("bwd_impl", ["scan", "pallas", "fused"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal, bwd_impl):
        """flash_attention is trainable: its custom-VJP blockwise
        backward must reproduce the dense reference's q/k/v gradients."""
        key = jax.random.PRNGKey(3)
        B, L, H, D = 2, 32, 2, 8
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))
        cot = jax.random.normal(jax.random.fold_in(key, 7), (B, L, H, D))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) * cot)

        g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(
            q, k, v)
        g_flash = jax.grad(
            loss(lambda q, k, v, causal: flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8,
                bwd_impl=bwd_impl)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("bwd_impl", ["scan", "pallas", "fused"])
    def test_gradients_block_q_not_multiple_of_block_k(self, bwd_impl):
        """Gradient twin of the partial-diagonal forward regression: the
        backward kernels' causal block-skip conditions must keep blocks
        PARTIALLY reached across an unaligned bq/bk diagonal."""
        key = jax.random.PRNGKey(11)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 48, 1, 8)) for i in range(3))

        def f(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        g_ref = jax.grad(
            f(lambda q, k, v: dot_product_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(
            f(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                              block_q=16, block_k=24,
                                              bwd_impl=bwd_impl)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("bwd_impl", ["scan", "pallas", "fused"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_rectangular(self, causal, bwd_impl):
        """Lq < Lk (decode-style): with causal=True the key blocks past
        Lq are fully masked and skipped in the backward — the
        zero dk/dv tail must still match the dense reference."""
        key = jax.random.PRNGKey(4)
        q = jax.random.normal(key, (1, 16, 1, 4))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 48, 1, 4))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 48, 1, 4))

        def f(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        g_ref = jax.grad(
            f(lambda q, k, v: dot_product_attention(q, k, v, causal=causal)),
            argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(
            f(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                              block_q=8, block_k=16,
                                              bwd_impl=bwd_impl)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_causal_grid_truncation_shape(self):
        """Causal square grids visit ONLY at-or-below-diagonal k-blocks:
        n(n+1)/2 of the n^2 full steps (the ~(n+1)/2n ratio), pinned on
        the step tables the packed grid scalar-prefetches and on the
        public accounting (flash_grid_info) tools/tpu_flash_check.py
        puts into its report."""
        from horovod_tpu.ops.attention import (_causal_step_tables,
                                               flash_grid_info)

        for n in (1, 2, 5, 8):
            g = flash_grid_info(n * 16, n * 16, causal=True, block_q=16,
                                block_k=16, head_dim=8)
            assert g["truncated"]
            assert g["steps"] == n * (n + 1) // 2
            assert g["steps_full"] == n * n
            assert g["kv_fetch_frac"] == round((n + 1) / (2 * n), 4)
        # Every enumerated pair intersects the mask's live region; the
        # k-major (dK/dV) walk enumerates exactly the same pairs.
        qi, kb = _causal_step_tables(8, 8, 16, 16)
        assert (kb * 16 <= qi * 16 + 15).all()
        qi_k, kb_k = _causal_step_tables(8, 8, 16, 16, k_major=True)
        assert qi_k.size == qi.size
        assert (set(zip(qi_k.tolist(), kb_k.tolist()))
                == set(zip(qi.tolist(), kb.tolist())))
        # Unaligned bq/bk diagonal (48 = 3x16 = 2x24): blocks PARTIALLY
        # reached across the diagonal stay enumerated.
        qi_u, kb_u = _causal_step_tables(3, 2, 16, 24)
        assert (kb_u * 24 <= qi_u * 16 + 15).all()
        assert qi_u.size == 3 + 1 + 1  # qi0->kb0, qi1->kb0..1, qi2->kb0..1
        # Non-causal, cross-attention (Lq != Lk), and offset-causal keep
        # the FULL grid; equal nonzero offsets are plain square causal.
        assert not flash_grid_info(64, 64, causal=False, block_q=8,
                                   block_k=8)["truncated"]
        assert not flash_grid_info(32, 64, causal=True, block_q=8,
                                   block_k=8)["truncated"]
        assert not flash_grid_info(64, 64, causal=True, q_offset=64,
                                   block_q=8, block_k=8)["truncated"]
        assert flash_grid_info(64, 64, causal=True, q_offset=128,
                               k_offset=128, block_q=8,
                               block_k=8)["truncated"]
        with pytest.raises(ValueError, match="truncate=True"):
            flash_grid_info(32, 64, causal=True, block_q=8, block_k=8,
                            truncate=True)

    def test_truncated_matches_full_grid(self):
        """The packed causal grid is bit-identical to the full grid's
        compute-skip path — forward AND the packed Pallas backward pair
        (truncate=False pins the full grid)."""
        key = jax.random.PRNGKey(13)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (2, 64, 2, 8)) for i in range(3))
        out_t = flash_attention(q, k, v, causal=True, block_q=16,
                                block_k=16)
        out_f = flash_attention(q, k, v, causal=True, block_q=16,
                                block_k=16, truncate=False)
        np.testing.assert_array_equal(np.asarray(out_t), np.asarray(out_f))

        def loss(truncate):
            return lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16,
                bwd_impl="pallas", truncate=truncate) ** 2)

        g_t = jax.grad(loss(None), argnums=(0, 1, 2))(q, k, v)
        g_f = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_t, g_f):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("bwd_impl", ["scan", "pallas", "fused"])
    def test_offset_causal_matches_reference(self, bwd_impl):
        """Global-offset causal (the ring/Ulysses shard geometry):
        queries are a suffix block at q_offset over a longer key range —
        the full-grid path with the shifted diagonal must match the
        dense reference for forward and both backward kernels."""
        key = jax.random.PRNGKey(17)
        q = jax.random.normal(key, (1, 16, 1, 8))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 48, 1, 8))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 48, 1, 8))
        ref = dot_product_attention(q, k, v, causal=True, q_offset=32)
        out = flash_attention(q, k, v, causal=True, q_offset=32,
                              block_q=8, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

        def f(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        g_ref = jax.grad(
            f(lambda q, k, v: dot_product_attention(q, k, v, causal=True,
                                                    q_offset=32)),
            argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(
            f(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                              q_offset=32, block_q=8,
                                              block_k=16,
                                              bwd_impl=bwd_impl)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("bwd_impl", ["scan", "pallas", "fused"])
    def test_truncated_odd_seq_default_blocks(self, bwd_impl):
        """Seq not a multiple of the preferred block ladder (40 -> the
        8-sublane floor): the truncated causal path must stay exact vs
        dense through the degraded tiling, forward and both backwards."""
        key = jax.random.PRNGKey(19)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (2, 40, 2, 8)) for i in range(3))
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        g_ref = jax.grad(lambda q: jnp.sum(
            dot_product_attention(q, k, v, causal=True) ** 2))(q)
        g_fl = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, bwd_impl=bwd_impl) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("case", [
        dict(shape=(2, 64, 2, 8), causal=True, block_q=16, block_k=16),
        dict(shape=(1, 96, 1, 8), causal=True, block_q=16, block_k=48),
        dict(shape=(1, 96, 1, 8), causal=True, block_q=48, block_k=16),
        dict(shape=(1, 64, 2, 8), causal=True, block_q=16, block_k=16,
             truncate=False),
        dict(shape=(1, 64, 2, 8), causal=False, block_q=16, block_k=32),
        dict(shape=(1, 32, 1, 8), causal=True, block_q=8, block_k=16,
             keys=64, q_offset=32),
    ], ids=["packed", "wide_keys", "tall_rows", "full_grid", "not_causal",
            "offset"])
    def test_fused_backward_equals_the_split(self, case):
        """The one-kernel backward runs the split's products in the split's
        order (a q-block's dQ rows summed over ascending k-blocks in float32,
        cast once): with several k-blocks a q-block, so that the resident dQ
        rows are revisited, its three gradients equal the two kernels' to
        the last bit, on the packed grid and on the full one."""
        case = dict(case)
        B, L, H, D = case.pop("shape")
        keys = case.pop("keys", L)
        key = jax.random.PRNGKey(23)
        q = jax.random.normal(key, (B, L, H, D))
        k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, keys, H, D))
                for i in (1, 2))

        def grads(bwd_impl):
            return jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, bwd_impl=bwd_impl, **case) ** 2), argnums=(0, 1, 2))(
                    q, k, v)

        for a, b in zip(grads("fused"), grads("pallas")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("truncate", [None, False],
                             ids=["packed", "full_grid"])
    @pytest.mark.parametrize("whole_projection", [False, True],
                             ids=["separate", "fused_projection"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_two_heads_a_program_equal_one(self, dtype, whole_projection,
                                           truncate, window):
        """Two heads of 64 a program, read by index map from ``[B, L, H x
        64]`` arrays (or from one fused ``[B, L, 3 x H x 64]`` projection)
        and written back the same way: every product takes one operand with
        the other head's lanes zeroed, so the sums gain exact zeros and o,
        dQ, dK and dV are the one-head programs' to the last bit, in float32
        and (the same roundings at the same places) in bfloat16; on the
        packed grid and on the full one, under a window too, at four blocks
        a side so that every scratch is revisited. Against the dense
        reference within this class's tolerances."""
        B, L, H, D = 2, 64, 4, 64
        key = jax.random.PRNGKey(29)
        q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i),
                                         (B, L, H, D), dtype)
                       for i in range(4))
        kw = dict(causal=True, block_q=16, block_k=16, truncate=truncate,
                  window=window)

        def flat(t):
            return t.reshape(B, L, H * D)

        def two(q, k, v):
            if whole_projection:
                out = flash_attention(
                    jnp.concatenate([flat(q), flat(k), flat(v)], -1),
                    heads=H, **kw)
            else:
                out = flash_attention(flat(q), flat(k), flat(v), heads=H,
                                      **kw)
            assert out.shape == (B, L, H * D)
            return out.reshape(B, L, H, D)

        one_out, one_vjp = jax.vjp(
            lambda *a: flash_attention(*a, **kw), q, k, v)
        two_out, two_vjp = jax.vjp(two, q, k, v)
        for a, b in zip((one_out, *one_vjp(do)), (two_out, *two_vjp(do))):
            assert a.dtype == b.dtype == dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        ref_out, ref_vjp = jax.vjp(
            lambda *a: dot_product_attention(*a, causal=True, window=window),
            *(t.astype(jnp.float32) for t in (q, k, v)))
        tol = 2e-5 if dtype == jnp.float32 else 6e-2
        for a, b in zip((ref_out, *ref_vjp(do.astype(jnp.float32))),
                        (two_out, *two_vjp(do))):
            np.testing.assert_allclose(np.asarray(b, np.float32),
                                       np.asarray(a), atol=tol, rtol=tol)

    def test_two_heads_a_program_refuse_what_they_cannot_read(self):
        """``heads`` asks for the projections' layout: an even number of
        heads of 64 in three ``[B, L, H x 64]`` arrays or one fused one, no
        shared key, the one-kernel backward."""
        x = jnp.ones((1, 32, 2 * 64))
        for args, kw in [
                ((x, x, x), dict(heads=3)),                 # odd
                ((x, x, x), dict(heads=4)),                 # not 4 x 64 wide
                ((x, x), dict(heads=2)),                    # no v
                ((x,), dict(heads=2)),                      # not 3 x 2 x 64
                ((x.reshape(1, 32, 2, 64),) * 3, dict(heads=2)),
                ((x, x, x), dict(heads=2, bwd_impl="pallas")),
                ((x, x, x), dict(heads=2, k_shared=jnp.ones((1, 32, 8))))]:
            with pytest.raises(ValueError, match="two heads a program"):
                flash_attention(*args, causal=True, block_q=8, block_k=8,
                                **kw)

    def test_causal_rejects_fully_masked_rows(self):
        """q_offset < k_offset leaves query rows with NO visible key —
        an undefined softmax where the kernel's 0-output would silently
        diverge from the dense reference's degenerate uniform rows. The
        contract is an explicit error, not a silent disagreement."""
        key = jax.random.PRNGKey(23)
        q = jax.random.normal(key, (1, 16, 1, 8))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, 1, 8))
        with pytest.raises(ValueError, match="q_offset >= k_offset"):
            flash_attention(q, k, k, causal=True, k_offset=16,
                            block_q=8, block_k=8)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, hvd, causal):
        mesh = _mesh({"sp": 8})
        key = jax.random.PRNGKey(2)
        B, L, H, D = 2, 64, 2, 8  # L_local = 8
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))
        ref = dot_product_attention(q, k, v, causal=causal)

        out = jax.jit(jax.shard_map(
            lambda a, b, c: par.ring_attention(a, b, c, "sp", causal=causal),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp")))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_causal_dead_block_skip_matches_dense(self, hvd):
        """The causal dead-block skip (lax.cond over fully-above-diagonal
        visiting blocks) pinned against dense for forward AND gradients.
        Forced on explicitly: the auto gate disables it on legacy
        runtimes, where the rank-divergent cond only transposes inside
        check_vma=False regions — exactly how this test runs it, so the
        cond path has CI coverage on every runtime."""
        mesh = _mesh({"sp": 8})
        key = jax.random.PRNGKey(21)
        B, L, H, D = 2, 64, 2, 8
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))
        fn = jax.shard_map(
            lambda a, b, c: par.ring_attention(a, b, c, "sp", causal=True,
                                               skip_dead_blocks=True),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)
        out = jax.jit(fn)(q, k, v)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, causal=True) ** 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-5)

    def test_grad_flows(self, hvd):
        mesh = _mesh({"sp": 4})
        key = jax.random.PRNGKey(3)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 16, 1, 4))
                   for i in range(3))

        def loss_sharded(q, k, v):
            fn = jax.shard_map(
                lambda a, b, c: par.ring_attention(a, b, c, "sp",
                                                   causal=True),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp"))
            return jnp.sum(fn(q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        g_sharded = jax.grad(loss_sharded)(q, k, v)
        g_dense = jax.grad(loss_dense)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_sharded),
                                   np.asarray(g_dense), atol=1e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, hvd, causal):
        mesh = _mesh({"sp": 4})
        key = jax.random.PRNGKey(4)
        B, L, H, D = 2, 32, 4, 8  # H == axis size
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))
        ref = dot_product_attention(q, k, v, causal=causal)
        out = jax.jit(jax.shard_map(
            lambda a, b, c: par.ulysses_attention(a, b, c, "sp",
                                                  causal=causal),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp")))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_head_divisibility_error(self, hvd):
        mesh = _mesh({"sp": 8})
        q = jnp.zeros((1, 16, 4, 8))  # 4 heads < 8 ranks
        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(
                lambda a: par.ulysses_attention(a, a, a, "sp"),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp")))(q)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_attn_fn_composes(self, hvd, causal):
        """The long-context flagship composition: after the head
        reshard, each chip runs FULL-sequence attention locally — which
        is exactly where the Pallas flash kernel belongs (attn_fn hook,
        ulysses_attention docstring). Forward AND gradients must match
        the dense reference; the kernel runs in interpret mode on the
        CPU mesh (class-1 check_vma opt-out, docs/parallelism.md)."""
        mesh = _mesh({"sp": 4})
        key = jax.random.PRNGKey(11)
        B, L, H, D = 2, 128, 4, 16  # flash blocks cover L after reshard
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, L, H, D))
                   for i in range(3))

        def flash(qh, kh, vh, causal, scale):
            return flash_attention(qh, kh, vh, causal=causal, scale=scale,
                                   block_q=32, block_k=32)

        def loss_sharded(q, k, v):
            fn = jax.shard_map(
                lambda a, b, c: par.ulysses_attention(
                    a, b, c, "sp", causal=causal, attn_fn=flash),
                mesh=mesh, in_specs=P(None, "sp"),
                out_specs=P(None, "sp"), check_vma=False)
            return jnp.sum(fn(q, k, v) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(
                dot_product_attention(q, k, v, causal=causal) ** 2)

        np.testing.assert_allclose(
            float(jax.jit(loss_sharded)(q, k, v)),
            float(loss_dense(q, k, v)), rtol=1e-5)
        g_sharded = jax.grad(loss_sharded, (0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
        for gs, gd in zip(g_sharded, g_dense):
            np.testing.assert_allclose(np.asarray(gs), np.asarray(gd),
                                       atol=1e-4)


class TestTensorParallel:
    def test_mlp_matches_dense(self, hvd):
        mesh = _mesh({"tp": 8})
        key = jax.random.PRNGKey(5)
        Din, Dh, B = 16, 32, 4
        x = jax.random.normal(key, (B, Din))
        w_up = jax.random.normal(jax.random.fold_in(key, 1), (Din, Dh)) * 0.1
        b_up = jax.random.normal(jax.random.fold_in(key, 2), (Dh,)) * 0.1
        w_dn = jax.random.normal(jax.random.fold_in(key, 3), (Dh, Din)) * 0.1
        b_dn = jax.random.normal(jax.random.fold_in(key, 4), (Din,)) * 0.1

        dense = (jax.nn.gelu(x @ w_up + b_up)) @ w_dn + b_dn

        out = jax.jit(jax.shard_map(
            lambda x, wu, bu, wd, bd: par.tp_mlp(x, wu, bu, wd, bd, "tp"),
            mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
            out_specs=P()))(x, w_up, b_up, w_dn, b_dn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5)

    def test_column_gather_output(self, hvd):
        mesh = _mesh({"tp": 4})
        x = jnp.ones((2, 8))
        w = jnp.arange(8 * 12, dtype=jnp.float32).reshape(8, 12) * 0.01
        dense = x @ w
        # Tiled all_gather replication the vma checker cannot infer.
        out = jax.jit(jax.shard_map(
            lambda x, w: par.column_parallel(x, w, axis="tp",
                                             gather_output=True),
            mesh=mesh, in_specs=(P(), P(None, "tp")),
            out_specs=P(), check_vma=False))(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5)

    def test_shard_helpers(self, hvd):
        w = jnp.arange(24, dtype=jnp.float32).reshape(4, 6)
        np.testing.assert_array_equal(
            np.asarray(par.shard_columns(w, 3, 1)), np.asarray(w[:, 2:4]))
        np.testing.assert_array_equal(
            np.asarray(par.shard_rows(w, 2, 1)), np.asarray(w[2:]))


class TestPipeline:
    def test_matches_sequential(self, hvd):
        mesh = _mesh({"pp": 4})
        key = jax.random.PRNGKey(6)
        D, M, Bm = 8, 6, 2  # 6 microbatches of 2 rows
        # Stage p: x -> tanh(x @ W_p + b_p); stack over stages.
        ws = jax.random.normal(key, (4, D, D)) * 0.3
        bs = jax.random.normal(jax.random.fold_in(key, 1), (4, D)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 2), (M, Bm, D))

        def stage(params, a):
            w, b = params
            return jnp.tanh(a @ w + b)

        expected = x
        for p in range(4):
            expected = jnp.tanh(expected @ ws[p] + bs[p])

        out = jax.jit(jax.shard_map(
            lambda params, x: par.pipeline_apply(stage, params, x, "pp"),
            mesh=mesh, in_specs=((P("pp"), P("pp")), P()),
            out_specs=P()))((ws, bs), x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   atol=1e-5)

    def test_gradients_match_sequential(self, hvd):
        """Pipeline gradients must equal the plain sequential autodiff —
        this pinned down a latent x(pp size) scaling from differentiating
        through the final raw psum (fixed via the exact-VJP sum_across)."""
        mesh = _mesh({"pp": 4})
        key = jax.random.PRNGKey(9)
        D, M, Bm = 8, 6, 2
        ws = jax.random.normal(key, (4, D, D)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1), (M, Bm, D))

        def stage(w, a):
            return jnp.tanh(a @ w)

        def seq_loss(ws):
            out = x
            for p in range(4):
                out = jnp.tanh(out @ ws[p])
            return jnp.mean(out ** 2)

        g_seq = jax.grad(seq_loss)(ws)

        def pipe_loss(ws, x):
            return jnp.mean(par.pipeline_apply(stage, ws, x, "pp") ** 2)

        g_pipe = jax.jit(jax.shard_map(
            jax.grad(pipe_loss), mesh=mesh, in_specs=(P("pp"), P()),
            out_specs=P("pp")))(ws, x)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                                   rtol=1e-5, atol=1e-6)

    def test_remat_gradients_match(self, hvd):
        """remat=True recomputes stage internals in backward; gradients
        must be identical to the stored-activation schedule."""
        mesh = _mesh({"pp": 4})
        key = jax.random.PRNGKey(8)
        D, M, Bm = 8, 6, 2
        ws = jax.random.normal(key, (4, D, D)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1), (M, Bm, D))

        def stage(w, a):
            return jnp.tanh(a @ w)

        def make_loss(remat):
            def loss(ws, x):
                out = par.pipeline_apply(stage, ws, x, "pp", remat=remat)
                return jnp.mean(out ** 2)

            return jax.jit(jax.shard_map(
                jax.grad(loss), mesh=mesh, in_specs=(P("pp"), P()),
                out_specs=P("pp")))

        g_plain = make_loss(False)(ws, x)
        g_remat = make_loss(True)(ws, x)
        np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_remat),
                                   rtol=1e-6, atol=1e-7)


def test_vma_checking_tracks_region(hvd):
    """Canary for the jax internal behind vma_checking(): the regime
    detector must read True/False inside matching shard_map regions —
    the typed/untyped gradient reductions branch on it, so a jax upgrade
    that moves the internal must fail THIS test loudly, not mis-scale
    gradients silently."""
    from horovod_tpu.parallel._vma import vma_checking

    seen = {}

    def probe(key):
        def f(x):
            seen[key] = vma_checking()
            return x
        return f

    m = _mesh({"sp": 8})
    jax.jit(jax.shard_map(probe("typed"), mesh=m, in_specs=P(),
                          out_specs=P()))(jnp.ones((4,)))
    jax.jit(jax.shard_map(probe("untyped"), mesh=m, in_specs=P(),
                          out_specs=P(), check_vma=False))(jnp.ones((4,)))
    assert seen == {"typed": True, "untyped": False}


