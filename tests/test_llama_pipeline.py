"""The pipeline schedules for the Llama family (``models/llama_pipeline``):
GPipe and 1F1B stages, GSPMD-composed and hand-sharded over tp, against the
single-device oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama
from torchmpi_tpu.models.llama_pipeline import (
    _decoder_layer_tp_manual, make_1f1b_train_step, make_pp_train_step,
    shard_params_pp)

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


def _data(cfg, B=4, L=16, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    return tokens, targets


@pytest.mark.heavy
class TestPipeline:
    """Pipeline TRAININGS against the single-device oracle: minutes of
    compile+train on the virtual mesh."""
    def test_pp_auto_flash_matches_single(self, devices):
        """GPipe stages with GSPMD-composed dp/tp (stage_tp='auto'): the
        flash kernel nests its shard_map over the axes pp left auto, and
        the step's loss is the plain single-device loss."""
        cfg = llama.Config(vocab=128, d_model=32, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=64)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=4, L=16)
        want = float(llama.make_loss_fn(cfg)(params, (tokens, targets)))
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        step, _ = make_pp_train_step(cfg, mesh, n_microbatches=2,
                                     lr=0.1, attn="flash")
        _, loss = step(shard_params_pp(params, mesh, cfg), tokens, targets)
        np.testing.assert_allclose(float(loss), want, rtol=1e-5)

    def test_pp_train_matches_single(self, devices):
        """Pipeline-parallel llama (layers as GPipe stages over pp) produces
        the same loss and updated params as plain single-mesh training."""
        cfg = llama.tiny()          # 2 layers -> pp=2, V=1
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=4, L=16)

        step, V = make_pp_train_step(cfg, mesh, n_microbatches=2,
                                     lr=0.05, loss_chunk=8)
        assert V == 1
        p_pp = shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
        p_pp, loss_pp = step(p_pp, tokens, targets)

        ref_loss_fn = llama.make_loss_fn(cfg)
        ref_l, ref_g = jax.value_and_grad(ref_loss_fn)(params,
                                                       (tokens, targets))
        np.testing.assert_allclose(float(loss_pp), float(ref_l), rtol=1e-5)
        ref_p = jax.tree.map(lambda p, g: p - 0.05 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(p_pp), jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_pp_multi_layer_stages(self, devices):
        """V > 1 layers per stage: 4-layer model over pp=2."""
        cfg = llama.Config(vocab=128, d_model=32, n_layers=4, n_heads=4,
                           n_kv_heads=2, d_ff=64, max_seq=32)
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        params = llama.init(jax.random.PRNGKey(1), cfg)
        tokens, targets = _data(cfg, B=4, L=16, seed=2)
        step, V = make_pp_train_step(cfg, mesh, n_microbatches=4,
                                     lr=0.05, remat="dots")
        assert V == 2
        p_pp = shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
        losses = []
        for _ in range(6):
            p_pp, loss = step(p_pp, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.2, losses

    def test_1f1b_3d_composed_matches_oracle(self, devices):
        """1F1B on the dp x pp x tp mesh: pp manual, dp/tp GSPMD-composed —
        legal under the scheduled lax.conds because every predicate
        depends only on (tick, stage) and is therefore uniform along the
        auto axes.  Full-model loss and updated params == oracle."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step, _ = make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                       lr=0.1)
        p1 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        p1, loss1 = step(p1, tokens, targets)
        ref_l, ref_g = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        np.testing.assert_allclose(float(loss1), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p1)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)

    def test_1f1b_train_matches_oracle(self, devices):
        """llama over the 1F1B schedule: FULL-model grads (stage vjps +
        last-stage norm/head loss-params + embed scatter-add from the
        pipeline-input gradients) must match the single-device oracle, and
        repeated steps converge."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step, V = make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                       lr=0.1)
        assert V == 1
        p1 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
        p1, loss1 = step(p1, tokens, targets)
        ref_l, ref_g = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        np.testing.assert_allclose(float(loss1), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p1)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
        losses = [float(loss1)]
        for _ in range(5):
            p1, loss = step(p1, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.2, losses

    def test_pp3d_matches_oracle(self, devices):
        """The 3-D dp x pp x tp step (VERDICT r03 item 2): stage params
        tp-sharded, micro-batches dp-sharded, pp manual — loss and the
        SGD-updated params must match the single-device oracle."""
        cfg = llama.tiny()          # 2 layers -> pp=2, V=1
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)

        step, V = make_pp_train_step(cfg, mesh, n_microbatches=2,
                                     lr=0.1)
        p3 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        # tp sharding reached the stage weights (not replicated):
        wq_sh = p3["layers"]["wq"].sharding.spec
        assert "tp" in tuple(wq_sh), wq_sh
        p3, loss3 = step(p3, tokens, targets)

        ref_loss_fn = llama.make_loss_fn(cfg)
        ref_l, ref_g = jax.value_and_grad(ref_loss_fn)(params,
                                                       (tokens, targets))
        np.testing.assert_allclose(float(loss3), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p3)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)

    def test_pp3d_manual_tp_stage_matches_oracle(self, devices):
        """stage_tp='manual': tp and dp join pp as manual shard_map axes,
        the stage body hand-writes the two Megatron psums and runs the
        flash kernels on its LOCAL head shard (the composition GSPMD
        cannot produce — it replicates the unpartitionable Pallas call).
        Loss and SGD-updated params must equal the single-device oracle."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step, V = make_pp_train_step(cfg, mesh, n_microbatches=2,
                                     lr=0.1, attn="flash",
                                     stage_tp="manual")
        p3 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        p3, loss3 = step(p3, tokens, targets)
        ref_l, ref_g = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        np.testing.assert_allclose(float(loss3), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p3)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
        # Validation: manual needs flash and a tp axis.
        with pytest.raises(ValueError, match="flash"):
            make_pp_train_step(cfg, mesh, n_microbatches=2,
                               stage_tp="manual")
        mesh_no_tp = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(ValueError, match="tp mesh axis"):
            make_pp_train_step(cfg, mesh_no_tp, n_microbatches=2,
                               attn="flash", stage_tp="manual")

    def test_1f1b_manual_tp_stage_matches_oracle(self, devices):
        """1F1B x manual-tp stage (the round-4 partial row): the cond-free
        packed schedule hosts the hand-sharded flash stage — explicit
        Megatron psums run unconditionally every tick (compute-always +
        mask), the f/g markers make the in-region vjps exact, and the
        stash stays 2S-1-bounded instead of GPipe's M.  Loss + SGD-updated
        params must equal the single-device oracle, and repeated steps
        converge."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step, V = make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                       lr=0.1, attn="flash",
                                       stage_tp="manual")
        assert V == 1
        p1 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        p1, loss1 = step(p1, tokens, targets)
        ref_l, ref_g = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        np.testing.assert_allclose(float(loss1), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p1)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
        losses = [float(loss1)]
        for _ in range(4):
            p1, loss = step(p1, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.2, losses
        # The ALTERNATING (cond-gated, stash <= S+1) schedule is oracle-
        # exact too: explicit collectives under the scheduled cond are
        # legal because every predicate is uniform across the tp/dp groups.
        step_a, _ = make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                         lr=0.1, attn="flash",
                                         stage_tp="manual",
                                         manual_schedule="alternating")
        pa = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        pa, loss_a = step_a(pa, tokens, targets)
        np.testing.assert_allclose(float(loss_a), float(ref_l), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(jax.device_get(pa)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
        # Validation parity with the GPipe manual stage.
        with pytest.raises(ValueError, match="flash"):
            make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                 stage_tp="manual")
        with pytest.raises(ValueError, match="manual_schedule"):
            make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                 attn="flash", stage_tp="manual",
                                 manual_schedule="bogus")
        mesh_no_tp = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(ValueError, match="tp mesh axis"):
            make_1f1b_train_step(cfg, mesh_no_tp, n_microbatches=4,
                                 attn="flash", stage_tp="manual")

    def test_pp3d_zero1_adam(self, devices):
        """3-D pp step with optax adam + ZeRO-1: optimizer moments shard
        over dp on top of the pp x tp layout and the step runs finite."""
        import optax

        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "pp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        opt = optax.adam(1e-2)
        p3 = shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        step, _ = make_pp_train_step(
      cfg, mesh, n_microbatches=2, optimizer=opt,
      opt_state_example=jax.eval_shape(opt.init, p3), zero1=True)
        opt_state = opt.init(p3)
        losses = []
        for _ in range(4):
            p3, opt_state, loss = step(p3, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0] - 0.2, losses

    def test_pp_step_rejects_moe(self, devices):
        cfg = llama.moe_tiny()
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(NotImplementedError):
            make_pp_train_step(cfg, mesh, n_microbatches=2)


def test_the_tp_manual_stage_refuses_qk_norm():
    """Its column shards cannot norm over the whole projection."""
    cfg = llama.Config(**{**llama.tiny().__dict__, "qk_norm": True})
    lp = jax.tree.map(lambda a: a[0],
                      llama.init(jax.random.PRNGKey(0), cfg)["layers"])
    h = jnp.zeros((1, 16, cfg.d_model))
    with pytest.raises(NotImplementedError, match="QK-norm"):
        _decoder_layer_tp_manual(cfg, lp, h, jnp.arange(16))
