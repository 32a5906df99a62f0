"""Tests for parallel/: mesh axes, tensor parallel, BlockSequential,
pipeline (reference analogues: test/blockSequential.lua unit tests,
examples/mnist/mnist_modelparallel.lua MPLinear semantics)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from torchmpi_tpu import parallel
from torchmpi_tpu.parallel import blocks as blocks_mod
from torchmpi_tpu.parallel import pipeline as pl
from torchmpi_tpu.parallel import tp


class TestMesh:
    def test_axis_order_canonical(self, devices):
        m = parallel.make_mesh({"tp": 4, "dp": 2}, devices=devices)
        assert m.axis_names == ("dp", "tp")
        assert m.shape["dp"] == 2 and m.shape["tp"] == 4

    def test_wildcard(self, devices):
        m = parallel.make_mesh({"dp": -1, "tp": 2}, devices=devices)
        assert m.shape["dp"] == 4

    def test_bad_product(self, devices):
        with pytest.raises(ValueError):
            parallel.make_mesh({"dp": 3, "tp": 2}, devices=devices)

    def test_three_axes(self, devices):
        m = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2}, devices=devices)
        assert m.axis_names == ("dp", "pp", "tp")


class TestTensorParallel:
    def test_mp_linear_matches_dense(self, devices):
        """MPLinear forward == dense forward (reference:
        mnist_modelparallel.lua partial-product + allreduce)."""
        mesh = parallel.make_mesh({"tp": 8}, devices=devices)
        params = tp.mp_linear_init(jax.random.PRNGKey(0), 32, 16)
        dense = jnp.asarray(np.random.RandomState(0).randn(4, 32), jnp.float32)
        want = dense @ params["w"] + params["b"]
        sharded = tp.shard_mp_linear(params, mesh)
        fn = tp.make_mp_linear(mesh)
        got = fn(sharded, dense)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                                   atol=1e-5)

    def test_mp_linear_grad_flows(self, devices):
        mesh = parallel.make_mesh({"tp": 8}, devices=devices)
        params = tp.shard_mp_linear(tp.mp_linear_init(jax.random.PRNGKey(0), 16, 8), mesh)
        x = jnp.ones((2, 16))
        fn = tp.make_mp_linear(mesh)

        def loss(p):
            return jnp.sum(fn(p, x) ** 2)

        g = jax.grad(loss)(params)
        assert float(jnp.sum(jnp.abs(g["w"]))) > 0

    def test_megatron_mlp_block(self, devices):
        """column -> activation -> row matches the dense computation with one
        forward psum."""
        mesh = parallel.make_mesh({"tp": 4, "dp": 2}, devices=devices)
        rng = np.random.RandomState(0)
        d, hidden = 12, 16
        w_up = jnp.asarray(rng.randn(d, hidden), jnp.float32)
        w_down = jnp.asarray(rng.randn(hidden, d), jnp.float32)
        b_up = jnp.asarray(rng.randn(hidden), jnp.float32)
        b_down = jnp.asarray(rng.randn(d), jnp.float32)
        x = jnp.asarray(rng.randn(2, d), jnp.float32)
        want = jax.nn.relu(x @ w_up + b_up) @ w_down + b_down

        def body(x, wu, bu, wd, bd):
            return tp.mlp_block(x, wu, bu, wd, bd)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
            out_specs=P(),
            check_vma=False,
        )
        got = fn(x, w_up, b_up, w_down, b_down)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                                   atol=2e-4)


class TestBlockSequential:
    def _layers(self, dims):
        layers = []
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            def mk(d_in=d_in, d_out=d_out):
                def init(rng):
                    return {"w": jax.random.normal(rng, (d_in, d_out)) * 0.1,
                            "b": jnp.zeros((d_out,))}

                def apply(p, x):
                    return jax.nn.relu(x @ p["w"] + p["b"])

                return init, apply
            layers.append(mk())
        return layers

    def test_partition_counts(self):
        """Partition into <=N contiguous blocks (reference:
        test/blockSequential.lua:14-30 partition counts)."""
        assert blocks_mod.partition_contiguous([10, 10, 10, 10], 2) == [(0, 2), (2, 4)]
        assert len(blocks_mod.partition_contiguous([1] * 7, 3)) == 3
        assert blocks_mod.partition_contiguous([5], 4) == [(0, 1)]
        assert blocks_mod.partition_contiguous([100, 1, 1, 1], 2) == [(0, 1), (1, 4)]

    def test_forward_equivalence(self):
        """Forward is identical before/after partitioning (reference:
        blockSequential.lua forward/backward equivalence)."""
        layers = self._layers([8, 16, 16, 4])
        seq = parallel.BlockSequential(layers, max_blocks=2)
        params = seq.init(jax.random.PRNGKey(0))
        x = jnp.ones((2, 8))
        want = x
        for (_, apply), p in zip(layers, params):
            want = apply(p, want)
        np.testing.assert_allclose(np.asarray(seq.apply(params, x)),
                                   np.asarray(want))

    def test_flatten_roundtrip(self):
        layers = self._layers([4, 8, 4])
        seq = parallel.BlockSequential(layers, max_blocks=2)
        params = seq.init(jax.random.PRNGKey(0))
        flat = seq.flatten_block(params, 0)
        rebuilt = seq.unflatten_block(params, 0, flat)
        a, b = seq.bounds[0]
        for orig, new in zip(params[a:b], rebuilt):
            for lo, ln in zip(jax.tree.leaves(orig), jax.tree.leaves(new)):
                np.testing.assert_allclose(np.asarray(lo), np.asarray(ln))

    def test_backward_step_matches_monolithic(self):
        """backward_step blocks reassemble to the monolithic gradient
        (reference: blockSequential.lua backwardStep == updateGradInput)."""
        layers = self._layers([6, 12, 6])
        seq = parallel.BlockSequential(layers, max_blocks=2)
        params = seq.init(jax.random.PRNGKey(0))
        x = jnp.ones((3, 6))

        def loss_fn(ps, x):
            return jnp.sum(seq.apply(ps, x) ** 2)

        want = jax.grad(lambda ps: loss_fn(ps, x))(params)
        got: dict = {}
        order = []
        for i, block_grads in seq.backward_step(loss_fn, params, x):
            order.append(i)
            a, b = seq.bounds[i]
            for j, g in enumerate(block_grads):
                got[a + j] = g
        assert order == sorted(order, reverse=True)  # last->first walk
        for j in range(len(params)):
            for lw, lg in zip(jax.tree.leaves(want[j]), jax.tree.leaves(got[j])):
                np.testing.assert_allclose(np.asarray(lw), np.asarray(lg), rtol=1e-6)


class TestPipeline:
    def test_pipeline_matches_sequential(self, devices):
        """GPipe over 4 stages == running the 4 blocks sequentially."""
        mesh = parallel.make_mesh({"pp": 4, "dp": 2}, devices=devices)
        d, mb, M = 8, 2, 4
        rng = np.random.RandomState(0)
        stage_params = [{"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)}
                        for _ in range(4)]

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        stacked = pl.stack_stage_params(stage_params)
        stacked = pl.stage_sharding(mesh, stacked)
        x = jnp.asarray(rng.randn(M * mb, d), jnp.float32)
        xm = pl.microbatch(x, M)
        fn = jax.jit(pl.make_pipeline_fn(mesh, stage_fn, n_microbatches=M))
        y = pl.unmicrobatch(fn(stacked, xm))

        want = x
        for p in stage_params:
            want = stage_fn(p, want)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                                   atol=1e-5)

    def test_pipeline_grad(self, devices):
        """jax.grad differentiates through the schedule (backward pipeline)."""
        mesh = parallel.make_mesh({"pp": 4, "dp": 2}, devices=devices)
        d, mb, M = 4, 2, 4
        rng = np.random.RandomState(1)
        stage_params = [{"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)}
                        for _ in range(4)]

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        stacked = pl.stage_sharding(mesh, pl.stack_stage_params(stage_params))
        x = jnp.asarray(rng.randn(M * mb, d), jnp.float32)
        xm = pl.microbatch(x, M)
        fn = pl.make_pipeline_fn(mesh, stage_fn, n_microbatches=M)

        def loss(params):
            return jnp.sum(fn(params, xm) ** 2)

        g = jax.jit(jax.grad(loss))(stacked)
        gn = float(jnp.sum(jnp.abs(g["w"])))
        assert np.isfinite(gn) and gn > 0
        # Check against the sequential model's gradient.
        def seq_loss(params_list):
            h = x
            for p in params_list:
                h = stage_fn(p, h)
            return jnp.sum(h ** 2)

        want = jax.grad(seq_loss)(stage_params)
        want_stacked = pl.stack_stage_params(want)
        np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(want_stacked["w"]),
                                   rtol=1e-4, atol=1e-5)

    def test_microbatch_roundtrip(self):
        x = jnp.arange(24.0).reshape(12, 2)
        m = pl.microbatch(x, 4)
        assert m.shape == (4, 3, 2)
        np.testing.assert_allclose(np.asarray(pl.unmicrobatch(m)), np.asarray(x))
        with pytest.raises(ValueError):
            pl.microbatch(x, 5)

    def test_sharded_io_matches_replicated(self, devices):
        """sharded_io=True (input shards ppermuted to stage 0, outputs
        shipped from the last stage — no psum broadcast) == replicated I/O,
        values and gradients."""
        mesh = parallel.make_mesh({"pp": 4, "dp": 2}, devices=devices)
        d, mb, M = 8, 2, 8
        rng = np.random.RandomState(2)
        stage_params = [{"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)}
                        for _ in range(4)]
        stage_fn = lambda p, h: jnp.tanh(h @ p["w"])
        stacked = pl.stage_sharding(mesh, pl.stack_stage_params(stage_params))
        xm = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

        f_sh = pl.make_pipeline_fn(mesh, stage_fn, M, sharded_io=True)
        f_re = pl.make_pipeline_fn(mesh, stage_fn, M, sharded_io=False)
        np.testing.assert_allclose(np.asarray(jax.jit(f_sh)(stacked, xm)),
                                   np.asarray(jax.jit(f_re)(stacked, xm)),
                                   rtol=1e-5, atol=1e-6)
        g_sh = jax.jit(jax.grad(lambda p: jnp.sum(f_sh(p, xm) ** 2)))(stacked)
        g_re = jax.jit(jax.grad(lambda p: jnp.sum(f_re(p, xm) ** 2)))(stacked)
        np.testing.assert_allclose(np.asarray(g_sh["w"]), np.asarray(g_re["w"]),
                                   rtol=1e-4, atol=1e-5)


class Test1F1B:
    def test_schedule_properties(self):
        """1F1B schedule: every (mb, stage) fwd/bwd exactly once in order,
        stash capped at S (GPipe stashes M), same tick count as GPipe."""
        for S, M in ((2, 4), (4, 8), (4, 16), (8, 8), (3, 5)):
            fs, bs, stash = pl.schedule_1f1b(S, M)
            for s in range(S):
                assert [m for m in fs[:, s] if m >= 0] == list(range(M))
                assert [m for m in bs[:, s] if m >= 0] == list(range(M))
            assert stash <= S, (S, M, stash)
            st = pl.pipeline_stats(S, M, "1f1b")
            assert st["max_stash"] <= S < pl.pipeline_stats(S, M, "gpipe")["max_stash"] or M <= S
            assert st["ticks"] == 2 * (M + S - 1), st

    def test_schedule_combined_properties(self):
        """Packed (combined) 1F1B schedule for the cond-free body: both
        slots per tick, every (mb, stage) fwd/bwd exactly once, stash
        capped at 2S-1 (M-independent), ticks ~= M + 2S - 1 — and the
        single-link-buffer invariant holds (generation raises otherwise)
        across the whole geometry grid the virtual mesh can host."""
        for S in range(2, 9):
            for M in list(range(1, 18)) + [32, 64]:
                fs, bs, stash = pl.schedule_1f1b(S, M, combined=True)
                for s in range(S):
                    assert [m for m in fs[:, s] if m >= 0] == list(range(M))
                    assert [m for m in bs[:, s] if m >= 0] == list(range(M))
                assert stash <= 2 * S - 1, (S, M, stash)
                if M >= 2 * S:
                    assert fs.shape[0] <= M + 2 * S, (S, M, fs.shape[0])
        st = pl.pipeline_stats(8, 64, "1f1b-combined")
        assert st["ticks"] < pl.pipeline_stats(8, 64, "1f1b")["ticks"]
        assert st["max_stash"] <= 15

    def test_1f1b_matches_sequential(self, devices):
        """1F1B loss and stage-stacked grads == sequential model autodiff."""
        S, M, d, mb = 4, 8, 16, 4
        mesh = parallel.make_mesh({"pp": S, "dp": 2}, devices=devices)
        rng = np.random.RandomState(3)
        stages = [{"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32),
                   "b": jnp.asarray(rng.randn(d) * 0.1, jnp.float32)}
                  for _ in range(S)]
        stacked = pl.stage_sharding(mesh, pl.stack_stage_params(stages))
        stage_fn = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
        loss_fn = lambda h, t: jnp.mean((h - t) ** 2)
        x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)
        tgt = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

        step = pl.make_1f1b_step(mesh, stage_fn, loss_fn, n_microbatches=M)
        loss, grads = jax.jit(step)(stacked, x, tgt)

        def ref(stacked_host):
            def apply_all(h):
                for s in range(S):
                    p = jax.tree.map(lambda a: a[s], stacked_host)
                    h = stage_fn(p, h)
                return h
            return jnp.mean(jnp.stack(
                [loss_fn(apply_all(x[m]), tgt[m]) for m in range(M)]))

        ref_l, ref_g = jax.value_and_grad(ref)(pl.stack_stage_params(stages))
        assert abs(float(loss) - float(ref_l)) < 1e-5
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
