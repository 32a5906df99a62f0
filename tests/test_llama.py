"""Llama-family model tests: geometry, forward/grad, tp sharding equivalence,
ring-attention path equivalence, and a dp x tp train step on the virtual mesh
(BASELINE config 5 shrunk to 8 CPU devices)."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama


def _data(cfg, B=4, L=16, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    return tokens, targets


class TestGeometry:
    def test_llama3_8b_param_count(self):
        """Llama-3-8B has ~8.03B parameters."""
        cfg = llama.llama3_8b()
        # Count analytically (no allocation): embed + layers + norm + head.
        hd = cfg.head_dim
        per_layer = (
            2 * cfg.d_model                                   # norms
            + cfg.d_model * cfg.n_heads * hd                  # wq
            + 2 * cfg.d_model * cfg.n_kv_heads * hd           # wk, wv
            + cfg.n_heads * hd * cfg.d_model                  # wo
            + 3 * cfg.d_model * cfg.d_ff                      # gate, up, down
        )
        total = (cfg.vocab * cfg.d_model + cfg.n_layers * per_layer
                 + cfg.d_model + cfg.d_model * cfg.vocab)
        assert 7.9e9 < total < 8.1e9, total

    def test_tiny_init_matches_count(self):
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        n = llama.num_params(params)
        assert n > 0
        shapes = jax.tree.map(lambda a: a.shape, params)
        assert shapes["layers"]["wq"] == (cfg.n_layers, cfg.d_model,
                                          cfg.n_heads * cfg.head_dim)


class TestForward:
    def test_logits_shape_and_grad(self):
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg)
        logits = jax.jit(lambda p, t: llama.apply(cfg, p, t))(params, tokens)
        assert logits.shape == (4, 16, cfg.vocab)
        assert logits.dtype == jnp.float32
        loss_fn = llama.make_loss_fn(cfg)
        loss, grads = jax.value_and_grad(loss_fn)(params, (tokens, targets))
        # Untrained loss ~= ln(vocab).
        assert abs(float(loss) - np.log(cfg.vocab)) < 1.0
        gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
        assert np.isfinite(gnorm) and gnorm > 0

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg, B=1)
        logits1 = llama.apply(cfg, params, tokens)
        tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % cfg.vocab)
        logits2 = llama.apply(cfg, params, tokens2)
        np.testing.assert_allclose(np.asarray(logits1[0, :-1]),
                                   np.asarray(logits2[0, :-1]), atol=1e-5)
        assert not np.allclose(np.asarray(logits1[0, -1]),
                               np.asarray(logits2[0, -1]))

    def test_bf16_compute(self):
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
        tokens, _ = _data(cfg)
        logits = llama.apply(cfg, params, tokens)
        assert logits.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(logits)))

    @pytest.mark.parametrize("cfg", [
        llama.tiny(),
        dataclasses.replace(llama.moe_tiny(), capacity_factor=None,
                            moe_z_coef=1e-3),
    ], ids=["dense", "moe-sorted-dropless"])
    def test_unrolled_matches_scan(self, cfg):
        """layer_loop='unroll' computes the same function as the scan:
        the logits, the loss and every gradient leaf — only the loop form
        differs.  The sorted dropless dispatch (``capacity_factor=None``) is
        the path the benchmark's OLMoE cell inlines."""
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg)
        a = llama.apply(cfg, params, tokens, layer_loop="scan")
        b = llama.apply(cfg, params, tokens, layer_loop="unroll")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        scan, unroll = (
            jax.value_and_grad(llama.make_loss_fn(cfg, layer_loop=loop))(
                params, (tokens, targets)) for loop in ("scan", "unroll"))
        assert abs(float(scan[0]) - float(unroll[0])) < 1e-5
        want, got = (jax.tree_util.tree_leaves_with_path(g)
                     for g in (scan[1], unroll[1]))
        assert [k for k, _ in want] == [k for k, _ in got]
        for (key, w), (_, g) in zip(want, got):
            assert np.any(np.asarray(w) != 0), jax.tree_util.keystr(key)
            np.testing.assert_allclose(
                np.asarray(w), np.asarray(g), rtol=1e-4, atol=1e-5,
                err_msg=jax.tree_util.keystr(key))

    @pytest.mark.parametrize("cfg,scanned", [
        (llama.tiny(), False),
        (llama.moe_tiny(), False),
        (dataclasses.replace(llama.tiny(),
                             n_layers=llama._INLINE_MAX_LAYERS), False),
        (dataclasses.replace(llama.tiny(),
                             n_layers=llama._INLINE_MAX_LAYERS + 1), True),
    ], ids=["tiny", "moe_tiny", "at-the-bound", "one-over-the-bound"])
    def test_default_loop_form_follows_depth(self, cfg, scanned):
        """Left to itself :func:`llama.apply` inlines a stack of at most
        ``_INLINE_MAX_LAYERS`` layers and scans a deeper one: the jaxpr
        holds a ``scan`` over the ``n_layers`` stacked layers, or none."""
        params = jax.eval_shape(
            lambda: llama.init(jax.random.PRNGKey(0), cfg))
        tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)

        def layer_scans(**how):
            jaxpr = jax.make_jaxpr(
                lambda p, t: llama.apply(cfg, p, t, **how))(params, tokens)
            return [e for e in jaxpr.eqns if e.primitive.name == "scan"
                    and e.params["length"] == cfg.n_layers]

        assert 2 <= cfg.n_layers
        assert len(layer_scans()) == (1 if scanned else 0)
        assert len(layer_scans(layer_loop="scan")) == 1
        assert len(layer_scans(layer_loop="unroll")) == 0


class TestGenerate:
    def test_greedy_matches_teacher_forced(self):
        """KV-cache decode == recomputing the full forward per step: the
        cached path must pick exactly the tokens full-context argmax picks."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=8)
        gen = llama.make_generate_fn(cfg, prompt_len=8, max_new=6)
        got = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        assert got.shape == (2, 6)

        seq = prompt
        for _ in range(6):
            logits = llama.apply(cfg, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        want = np.asarray(seq[:, 8:])
        np.testing.assert_array_equal(got, want)

    def test_sampled_generation_valid(self):
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=4)
        gen = llama.make_generate_fn(cfg, prompt_len=4, max_new=5,
                                     temperature=0.8)
        a = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        b = np.asarray(gen(params, prompt, jax.random.PRNGKey(2)))
        assert a.shape == (2, 5)
        assert ((a >= 0) & (a < cfg.vocab)).all()
        assert not np.array_equal(a, b)   # different keys, different samples

    def test_top_k_one_is_greedy(self):
        """top_k=1 at any temperature must reproduce greedy decoding."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=4)
        greedy = llama.make_generate_fn(cfg, prompt_len=4, max_new=5)
        k1 = llama.make_generate_fn(cfg, prompt_len=4, max_new=5,
                                    temperature=1.5, top_k=1)
        np.testing.assert_array_equal(
            np.asarray(greedy(params, prompt, jax.random.PRNGKey(1))),
            np.asarray(k1(params, prompt, jax.random.PRNGKey(2))))

    def test_top_k_top_p_restrict_support(self):
        """Sampled tokens stay inside the filtered support: per-position
        top-k sampling only emits tokens among the k highest-probability
        continuations, and tiny top_p collapses to greedy."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=1, L=4)
        K = 3
        genk = llama.make_generate_fn(cfg, prompt_len=4, max_new=1,
                                      temperature=1.0, top_k=K)
        # The first generated token's allowed support from full-context
        # logits:
        logits = np.asarray(llama.apply(cfg, params, prompt)[:, -1])
        allowed = set(np.argsort(-logits[0])[:K].tolist())
        seen = set()
        for s in range(40):
            t = int(np.asarray(genk(params, prompt,
                                    jax.random.PRNGKey(s)))[0, 0])
            seen.add(t)
        assert seen <= allowed, (seen, allowed)
        assert len(seen) > 1, "top-k sampling degenerated to one token"
        # Nucleus with tiny p keeps only the top token -> greedy.
        genp = llama.make_generate_fn(cfg, prompt_len=4, max_new=5,
                                      temperature=1.5, top_p=1e-6)
        greedy = llama.make_generate_fn(cfg, prompt_len=4, max_new=5)
        np.testing.assert_array_equal(
            np.asarray(genp(params, prompt, jax.random.PRNGKey(3))),
            np.asarray(greedy(params, prompt, jax.random.PRNGKey(4))))

    def test_sampler_validation(self):
        cfg = llama.tiny()
        with pytest.raises(ValueError, match="top_p"):
            llama.make_generate_fn(cfg, 4, 4, top_p=1.5)
        with pytest.raises(ValueError, match="top_k"):
            llama.make_generate_fn(cfg, 4, 4, top_k=-1)
        # Filters without a positive temperature would be silently greedy.
        with pytest.raises(ValueError, match="temperature"):
            llama.make_generate_fn(cfg, 4, 4, top_k=5)

    def test_validation(self):
        cfg = llama.tiny()
        with pytest.raises(ValueError, match=">= 1"):
            llama.make_generate_fn(cfg, prompt_len=0, max_new=4)

    def test_tp_sharded_decode_matches(self, devices):
        """Megatron-sharded params flow through the same compiled generate
        fn — GSPMD partitions the decode matmuls over tp — with identical
        tokens."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=8)
        gen = llama.make_generate_fn(cfg, prompt_len=8, max_new=6)
        want = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        mesh = parallel.make_mesh({"dp": 2, "tp": 4}, devices=devices)
        sharded = llama.shard_params(params, mesh, cfg)
        got = np.asarray(gen(sharded, prompt, jax.random.PRNGKey(1)))
        if not np.array_equal(got, want):
            # Partitioned reductions can flip a near-tied argmax without the
            # decode math being wrong; in that case require the underlying
            # logits to agree to the same tolerance the TP forward test
            # uses, so only genuine sharding bugs fail here.
            lg_u = np.asarray(llama.apply(cfg, params, prompt))
            lg_s = np.asarray(llama.apply(cfg, sharded, prompt, mesh=mesh))
            np.testing.assert_allclose(lg_s, lg_u, rtol=2e-4, atol=2e-4)

    def test_distributed_generate_token_exact(self, devices):
        """mesh-aware generation (VERDICT r04 item 2): weights stay in
        their Megatron layout, the batch shards over dp, and the K/V cache
        is PINNED dp x tp-sharded through prefill and every decode tick —
        tokens must equal the single-device oracle's, and the compiled
        program's carried cache must actually BE tp-sharded (no replicated
        cache: at full 8B width a replicated cache + gathered weights are
        what make single-chip sampling impossible)."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=4, L=8)
        gen = llama.make_generate_fn(cfg, prompt_len=8, max_new=6)
        want = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        mesh = parallel.make_mesh({"dp": 2, "tp": 2},
                                  devices=devices[:4])
        sharded = llama.shard_params(params, mesh, cfg)
        gen_tp = llama.make_generate_fn(cfg, prompt_len=8, max_new=6,
                                        mesh=mesh)
        got = np.asarray(gen_tp(sharded, prompt, jax.random.PRNGKey(1)))
        np.testing.assert_array_equal(got, want)
        # The pinned cache sharding reached the compiled per-device
        # program: the cache buffers appear at their LOCAL shard shape —
        # batch 4/dp2=2, KV heads 2/tp2=1 — and never at the replicated
        # global shape (the regression this guards: dropping the carry
        # re-pin lets GSPMD settle on a replicated cache, which is what
        # makes 8B-width sampling impossible).
        hlo = gen_tp.lower(sharded, prompt,
                           jax.random.PRNGKey(1)).compile().as_text()
        hd, nl, ml = cfg.head_dim, cfg.n_layers, 8 + 6
        local = f"f32[{nl},2,{ml},1,{hd}]"    # (layers, B/dp, max_len, KV/tp, hd)
        replicated = f"f32[{nl},4,{ml},2,{hd}]"
        assert local in hlo, f"sharded cache shape {local} not in HLO"
        assert replicated not in hlo, "cache appears replicated in HLO"
        # Validation: tp must divide the KV heads the cache shards on.
        import dataclasses
        cfg_kv1 = dataclasses.replace(cfg, n_kv_heads=1)
        with pytest.raises(ValueError, match="n_kv_heads"):
            llama.make_generate_fn(cfg_kv1, 8, 4, mesh=mesh)
        # Sampled generation composes with the mesh too (shape + support).
        gen_s = llama.make_generate_fn(cfg, prompt_len=8, max_new=5,
                                       temperature=0.8, top_k=8, mesh=mesh)
        out = np.asarray(gen_s(sharded, prompt, jax.random.PRNGKey(2)))
        assert out.shape == (4, 5) and out.min() >= 0 and out.max() < cfg.vocab


@pytest.mark.heavy
class TestSharded:
    """Multi-config sharded TRAININGS (equivalence across mesh shapes):
    minutes of compile+train on the virtual mesh — heavy; the fast loop
    keeps TestForward/TestGenerate as the llama core path."""
    def test_tp_matches_unsharded(self, devices):
        """dp x tp forward == single-device forward (GSPMD correctness)."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg)
        want = llama.apply(cfg, params, tokens)
        mesh = parallel.make_mesh({"dp": 2, "tp": 4}, devices=devices)
        sharded = llama.shard_params(params, mesh, cfg)
        got = jax.jit(lambda p, t: llama.apply(cfg, p, t, mesh=mesh))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_attention_matches_full(self, devices):
        """attn='ring' (sp over the ICI ring) == attn='full'."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg, B=2, L=32)
        mesh = parallel.make_mesh({"dp": 2, "sp": 4}, devices=devices)
        want = llama.apply(cfg, params, tokens)
        got = jax.jit(
            lambda p, t: llama.apply(cfg, p, t, mesh=mesh, attn="ring")
        )(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_flash_dp_tp_matches_full(self, devices):
        """attn='flash' on a dp x tp mesh == attn='full', loss and grads.
        The kernel must sit in a shard_map over batch and heads: the TPU
        compiler refuses to partition a Mosaic kernel under GSPMD (only
        interpret mode, plain XLA ops, ever let that pass on this mesh)."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        batch = _data(cfg, B=4, L=32)
        mesh = parallel.make_mesh({"dp": 2, "tp": 2}, devices=devices[:4])
        sharded = llama.shard_params(params, mesh, cfg)
        out = {}
        for attn in ("full", "flash"):
            fn = jax.value_and_grad(llama.make_loss_fn(cfg, mesh, attn=attn))
            out[attn] = jax.jit(fn)(sharded, batch)
        assert "shard_map" in str(jax.make_jaxpr(
            llama.make_loss_fn(cfg, mesh, attn="flash"))(sharded, batch))
        np.testing.assert_allclose(float(out["flash"][0]),
                                   float(out["full"][0]), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(out["flash"][1]),
                        jax.tree.leaves(out["full"][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

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
        step, _ = llama.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                           lr=0.1, attn="flash")
        _, loss = step(llama.shard_params_pp(params, mesh, cfg),
                       tokens, targets)
        np.testing.assert_allclose(float(loss), want, rtol=1e-5)

    def test_ring_native_gqa_traffic(self, devices):
        """The ring circulates K/V at n_kv_heads (not repeated to n_heads):
        the compiled sp program's collective-permute payload must scale with
        KV, which the parity test above already proves numerically; here we
        assert the un-repeated shapes reach the shard_map body."""
        cfg = llama.tiny()  # n_heads=4, n_kv_heads=2
        assert cfg.n_kv_heads < cfg.n_heads
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg, B=2, L=32)
        mesh = parallel.make_mesh({"dp": 2, "sp": 4}, devices=devices)
        jaxpr = jax.make_jaxpr(
            lambda p, t: llama.apply(cfg, p, t, mesh=mesh, attn="ring")
        )(params, tokens)
        # No repeat of K to n_heads before the ring: the only ppermute
        # operands are KV-headed.  The flash ring folds batch and heads into
        # the kernel grid dim, so per-device operands under dp=2, sp=4 are
        # (B/dp * KV = KV, L/sp=8, hd) — a full-head repeat would circulate
        # (B/dp * H, 8, hd) instead.
        text = str(jaxpr)
        kv_shape = f"[{cfg.n_kv_heads},8,{cfg.head_dim}]"
        full_shape = f"[{cfg.n_heads},8,{cfg.head_dim}]"
        ppermute_lines = [ln for ln in text.splitlines() if "ppermute" in ln]
        assert ppermute_lines, "ring produced no ppermute"
        assert any(kv_shape in ln for ln in ppermute_lines), ppermute_lines[:4]
        assert not any(full_shape in ln for ln in ppermute_lines), \
            "K/V were repeated to full head count before the ring"

    def test_remat_matches_dense(self, devices):
        """remat='dots'/'full' change memory, not values: loss and grads
        agree with the unremated forward."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=2, L=16)
        base = jax.value_and_grad(llama.make_loss_fn(cfg))(params, (tokens, targets))
        for remat in ("dots", "full"):
            loss, grads = jax.value_and_grad(
                llama.make_loss_fn(cfg, remat=remat))(params, (tokens, targets))
            np.testing.assert_allclose(float(loss), float(base[0]), rtol=1e-6)
            for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(base[1])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_chunked_loss_matches_dense(self):
        """loss_chunk computes identical loss/grads without the (B, L, V)
        logits; also validates the divisibility check."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=2, L=16)
        dense = jax.value_and_grad(llama.make_loss_fn(cfg))(params, (tokens, targets))
        chunked = jax.value_and_grad(
            llama.make_loss_fn(cfg, loss_chunk=4))(params, (tokens, targets))
        np.testing.assert_allclose(float(chunked[0]), float(dense[0]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(chunked[1]), jax.tree.leaves(dense[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="not divisible"):
            llama.make_loss_fn(cfg, loss_chunk=5)(params, (tokens, targets))

    def test_pp_train_matches_single(self, devices):
        """Pipeline-parallel llama (layers as GPipe stages over pp) produces
        the same loss and updated params as plain single-mesh training."""
        cfg = llama.tiny()          # 2 layers -> pp=2, V=1
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=4, L=16)

        step, V = llama.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                           lr=0.05, loss_chunk=8)
        assert V == 1
        p_pp = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
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
        step, V = llama.make_pp_train_step(cfg, mesh, n_microbatches=4,
                                           lr=0.05, remat="dots")
        assert V == 2
        p_pp = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
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
        step, _ = llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                             lr=0.1)
        p1 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        p1, loss1 = step(p1, tokens, targets)
        ref_l, ref_g = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        np.testing.assert_allclose(float(loss1), float(ref_l), rtol=2e-4)
        ref_p = jax.tree.map(lambda p, g: p - 0.1 * g, params, ref_g)
        for a, b in zip(jax.tree.leaves(jax.device_get(p1)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)

    def test_ring_zigzag_loss_and_grads_match(self, devices):
        """attn='ring-zigzag' (balanced causal ring): the loss permutes
        tokens/targets/RoPE-positions into the zigzag layout, so loss and
        grads equal the contiguous full-attention oracle exactly while
        every sp device computes equal block area per ring step."""
        cfg = llama.tiny(seq=128)
        mesh = parallel.make_mesh({"dp": 1, "sp": 8}, devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=2, L=128)
        sharded = llama.shard_params(params, mesh, cfg)
        l_full, g_full = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        lf = llama.make_loss_fn(cfg, mesh=mesh, attn="ring-zigzag")
        l_zz, g_zz = jax.value_and_grad(lf)(sharded, (tokens, targets))
        np.testing.assert_allclose(float(l_zz), float(l_full), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(g_zz), jax.tree.leaves(g_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=2e-4)
        step = llama.make_train_step(cfg, mesh, lr=0.3, attn="ring-zigzag")
        p, losses = sharded, []
        for _ in range(4):
            p, _, loss = step(p, None, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.3, losses

    def test_ring_zigzag_composes_with_tp(self, devices):
        """Zigzag on the 3-axis dp x sp x tp mesh (heads tp-sharded inside
        the balanced ring — the Megatron-SP composition) still equals the
        contiguous oracle exactly."""
        cfg = llama.tiny(seq=128)
        mesh = parallel.make_mesh({"dp": 2, "sp": 2, "tp": 2},
                                  devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=2, L=64)
        sharded = llama.shard_params(params, mesh, cfg)
        l_full, g_full = jax.value_and_grad(
            llama.make_loss_fn(cfg))(params, (tokens, targets))
        l_zz, g_zz = jax.value_and_grad(
            llama.make_loss_fn(cfg, mesh=mesh, attn="ring-zigzag"))(
            sharded, (tokens, targets))
        np.testing.assert_allclose(float(l_zz), float(l_full), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(g_zz), jax.tree.leaves(g_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=2e-4)

    def test_1f1b_train_matches_oracle(self, devices):
        """llama over the 1F1B schedule: FULL-model grads (stage vjps +
        last-stage norm/head loss-params + embed scatter-add from the
        pipeline-input gradients) must match the single-device oracle, and
        repeated steps converge."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step, V = llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                             lr=0.1)
        assert V == 1
        p1 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh)
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

        step, V = llama.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                           lr=0.1)
        p3 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
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
        step, V = llama.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                           lr=0.1, attn="flash",
                                           stage_tp="manual")
        p3 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
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
            llama.make_pp_train_step(cfg, mesh, n_microbatches=2,
                                     stage_tp="manual")
        mesh_no_tp = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(ValueError, match="tp mesh axis"):
            llama.make_pp_train_step(cfg, mesh_no_tp, n_microbatches=2,
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
        step, V = llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                             lr=0.1, attn="flash",
                                             stage_tp="manual")
        assert V == 1
        p1 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
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
        step_a, _ = llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                               lr=0.1, attn="flash",
                                               stage_tp="manual",
                                               manual_schedule="alternating")
        pa = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        pa, loss_a = step_a(pa, tokens, targets)
        np.testing.assert_allclose(float(loss_a), float(ref_l), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(jax.device_get(pa)),
                        jax.tree.leaves(ref_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=3e-4)
        # Validation parity with the GPipe manual stage.
        with pytest.raises(ValueError, match="flash"):
            llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                       stage_tp="manual")
        with pytest.raises(ValueError, match="manual_schedule"):
            llama.make_1f1b_train_step(cfg, mesh, n_microbatches=4,
                                       attn="flash", stage_tp="manual",
                                       manual_schedule="bogus")
        mesh_no_tp = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(ValueError, match="tp mesh axis"):
            llama.make_1f1b_train_step(cfg, mesh_no_tp, n_microbatches=4,
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
        p3 = llama.shard_params_pp(jax.tree.map(jnp.copy, params), mesh, cfg)
        step, _ = llama.make_pp_train_step(
            cfg, mesh, n_microbatches=2, optimizer=opt,
            opt_state_example=jax.eval_shape(opt.init, p3), zero1=True)
        opt_state = opt.init(p3)
        losses = []
        for _ in range(4):
            p3, opt_state, loss = step(p3, opt_state, tokens, targets)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses), losses
        assert losses[-1] < losses[0] - 0.2, losses

    def test_three_axis_ring_tp_matches(self, devices):
        """dp x sp x tp: ring attention with heads sharded over tp
        (Megatron-SP composition) == unsharded forward, and the full train
        step converges on the 3-axis mesh."""
        cfg = llama.tiny()   # H=4, KV=2 — both divide tp=2
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = _data(cfg, B=4, L=32)
        want = llama.apply(cfg, params, tokens)
        mesh = parallel.make_mesh({"dp": 2, "sp": 2, "tp": 2},
                                  devices=devices)
        sharded = llama.shard_params(params, mesh, cfg)
        got = jax.jit(
            lambda p, t: llama.apply(cfg, p, t, mesh=mesh, attn="ring")
        )(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        step = llama.make_train_step(cfg, mesh, lr=0.5, attn="ring")
        losses = []
        p3 = sharded
        for _ in range(5):
            p3, _, loss = step(p3, None, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.5, losses

    def test_ring_tp_indivisible_heads_fall_back(self, devices):
        """KV=2 does not divide tp=4: heads replicate over tp (correct,
        just less efficient) instead of mis-sharding."""
        cfg = llama.tiny()   # KV=2
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg, B=2, L=32)
        want = llama.apply(cfg, params, tokens)
        mesh = parallel.make_mesh({"sp": 2, "tp": 4}, devices=devices)
        sharded = llama.shard_params(params, mesh, cfg)
        got = jax.jit(
            lambda p, t: llama.apply(cfg, p, t, mesh=mesh, attn="ring")
        )(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_zero1_matches_plain_adam(self, devices):
        """make_train_step(zero1=True): optimizer moments shard over dp with
        the per-parameter tp layout preserved (path-suffix matching: wq
        column- vs wo row-sharded share a shape), and training is
        numerically identical to the replicated-state step."""
        import optax

        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "tp": 4}, devices=devices)
        opt = optax.adam(1e-3)
        params = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                    mesh, cfg)
        oex = jax.eval_shape(opt.init, params)
        osh = llama._zero1_opt_shardings(cfg, mesh, oex)
        assert str(osh[0].mu["layers"]["wq"].spec) == \
            "PartitionSpec('dp', None, 'tp')"
        assert str(osh[0].mu["layers"]["wo"].spec) == \
            "PartitionSpec('dp', 'tp', None)"
        step_z = llama.make_train_step(cfg, mesh, optimizer=opt, zero1=True,
                                       opt_state_example=oex)
        step_n = llama.make_train_step(cfg, mesh, optimizer=opt)
        tokens, targets = _data(cfg, B=8, L=16)
        oz = jax.jit(opt.init, out_shardings=osh)(params)
        on = opt.init(params)
        pz = params
        pn = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                mesh, cfg)
        for _ in range(4):
            pz, oz, lz = step_z(pz, oz, tokens, targets)
            pn, on, ln = step_n(pn, on, tokens, targets)
            assert abs(float(lz) - float(ln)) < 2e-4, (float(lz), float(ln))

    def test_zero1_validation(self, devices):
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "tp": 4}, devices=devices)
        with pytest.raises(ValueError):
            llama.make_train_step(cfg, mesh, zero1=True)

    def test_train_step_loss_decreases(self, devices):
        """dp x tp train step: loss falls on a repeated batch."""
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 2, "tp": 4}, devices=devices)
        params = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                    mesh, cfg)
        tokens, targets = _data(cfg, B=8, L=16)
        step = llama.make_train_step(cfg, mesh, lr=0.05)
        losses = []
        opt_state = None
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.3, losses


def _head_loss_dots(fn, *args):
    """(computation, result shape) of every dot the compiled ``fn`` runs
    under the ``head_loss`` scope, from the executable's ``op_name``s."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    found, where = [], None
    for line in text.splitlines():
        if line and not line[0].isspace():
            where = line.split()[0]
        if "head_loss" in line and re.search(r" (dot|convolution)\(", line):
            shape = re.search(r" = \w+\[([\d,]*)\]", line).group(1)
            found.append((where, tuple(int(n) for n in shape.split(","))))
    return found, text


class TestChunkedHead:
    """The chunked output head (``make_loss_fn(loss_chunk=C)``): a
    ``custom_vjp`` whose forward pass takes the head's gradients chunk by
    chunk, so the logits are formed once a step."""

    B, L, C = 2, 16, 4

    def _args(self, cfg):
        params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
        tokens = jax.ShapeDtypeStruct((self.B, self.L), jnp.int32)
        return params, (tokens, tokens)

    @pytest.mark.parametrize("remat", ["none", "dots", "full"])
    def test_three_products_a_chunk_under_grad(self, remat):
        """(a) ``value_and_grad``: the scan's body holds the three products
        the mathematics needs (logits, ``dh``, ``dW``) and no second
        ``h_c @ head``; no other computation holds a product of the head."""
        cfg = llama.tiny()
        loss_fn = llama.make_loss_fn(cfg, loss_chunk=self.C, remat=remat)
        dots, text = _head_loss_dots(jax.value_and_grad(loss_fn),
                                     *self._args(cfg))
        rows, D, V = self.B * self.C, cfg.d_model, cfg.vocab
        assert sorted(shape for _, shape in dots) == sorted(
            [(rows, V), (rows, D), (D, V)]), dots
        assert len({where for where, _ in dots}) == 1, dots
        assert "rematted_computation/head_loss" not in text

    def test_train_step_holds_the_same_three(self, devices):
        """(a) the same in ``make_train_step``'s program, MoE and flash."""
        cfg = llama.moe_tiny()
        mesh = parallel.make_mesh({"dp": 1}, devices=devices[:1])
        step = llama.make_train_step(cfg, mesh, attn="flash", remat="dots",
                                     loss_chunk=self.C)
        params, (tokens, _) = self._args(cfg)
        dots, _ = _head_loss_dots(step, params, None, tokens, tokens)
        assert len(dots) == 3 and len({where for where, _ in dots}) == 1, dots

    def test_forward_only_is_one_product_a_chunk(self):
        """(c) no gradient asked: one product a chunk, no (D, V) accumulator
        and no (B, L, D) one in the scan."""
        cfg = llama.tiny()
        dots, text = _head_loss_dots(
            llama.make_loss_fn(cfg, loss_chunk=self.C), *self._args(cfg))
        assert [shape for _, shape in dots] == [(self.B * self.C, cfg.vocab)]
        # what the scope's instructions make; the head itself rides through
        # the loop as an element of its tuple
        made = {m.group(1) for line in text.splitlines() if "head_loss" in line
                for m in [re.search(r" = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", line)]
                if m and m.group(2) not in ("get-tuple-element", "parameter")}
        assert f"{cfg.d_model},{cfg.vocab}" not in made
        assert f"{self.B},{self.L},{cfg.d_model}" not in made

    @pytest.mark.parametrize("scale", [1.0, 3.0])
    @pytest.mark.parametrize("chunks", [1, 2, 8])
    def test_matches_dense(self, chunks, scale):
        """(b) loss and every gradient leaf against the dense head, float32,
        with a cotangent of 1 and of 3 (``bwd`` scales what ``fwd`` took)."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        batch = _data(cfg, B=self.B, L=self.L)
        dense = jax.value_and_grad(llama.make_loss_fn(cfg))(params, batch)
        loss_fn = llama.make_loss_fn(cfg, loss_chunk=self.L // chunks)
        loss, grads = jax.value_and_grad(
            lambda p, b: scale * loss_fn(p, b))(params, batch)
        np.testing.assert_allclose(float(loss), scale * float(dense[0]),
                                   rtol=1e-6)
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        for (path, a), b in zip(flat, jax.tree.leaves(dense[1])):
            b = scale * np.asarray(b)
            np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-5, atol=1e-6 * np.abs(b).max(),
                err_msg=jax.tree_util.keystr(path))

    def test_bf16_gradients_keep_their_dtypes(self):
        """bfloat16 weights: gradients come back in the leaves' dtypes and
        near the float32 ones (the accumulator over chunks is the head's)."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        batch = _data(cfg, B=self.B, L=self.L)
        exact = jax.grad(llama.make_loss_fn(cfg))(params, batch)
        half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        grads = jax.grad(llama.make_loss_fn(cfg, loss_chunk=self.C))(half, batch)
        for name in ("head", "embed"):
            assert grads[name].dtype == jnp.bfloat16
            err = np.abs(np.asarray(grads[name], np.float32)
                         - np.asarray(exact[name]))
            assert err.max() < 0.05 * np.abs(np.asarray(exact[name])).max()


@pytest.mark.heavy
class TestLongContextRing:
    """attn='ring' (flash-composed) at a long-context geometry: L=2048 over
    sp=8 gives L_local=256 — the per-device score matrix the einsum ring
    would materialize is 16x the flash ring's whole block working set.  One
    train step must produce a finite loss and finite grads (the L=32k shape
    regime scaled to what the CPU interpreter can run; the composition is
    length-uniform, so the structure, not the constant, is what's proven)."""

    def test_long_prompt_prefill_uses_flash_and_matches(self, monkeypatch,
                                                        devices):
        """Prefill auto-selects the flash kernels at prompt >= 1024 (the
        (Lp, Lp) score matrix is the memory term) — asserted via a spy, so
        a regressed gate cannot pass silently — and generation must stay
        token-exact vs teacher-forced full-context argmax."""
        cfg = llama.tiny(seq=2048)
        params = llama.init(jax.random.PRNGKey(0), cfg)
        Lp = 1024
        rng = np.random.RandomState(3)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (1, Lp)), jnp.int32)

        chosen = []
        real = llama._make_attn_impl

        def spy(cfg_, attn_, mesh_, scale_):
            chosen.append(attn_)
            return real(cfg_, attn_, mesh_, scale_)

        monkeypatch.setattr(llama, "_make_attn_impl", spy)
        gen = llama.make_generate_fn(cfg, prompt_len=Lp, max_new=3)
        got = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        assert "flash" in chosen, chosen
        seq = prompt
        for _ in range(3):
            logits = llama.apply(cfg, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(got, np.asarray(seq[:, Lp:]))

    def test_train_step_long_context(self, devices):
        cfg = llama.tiny()
        mesh = parallel.make_mesh({"dp": 1, "sp": 8}, devices=devices)
        params = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                    mesh, cfg)
        tokens, targets = _data(cfg, B=1, L=2048)
        step = llama.make_train_step(cfg, mesh, lr=0.1, attn="ring")
        params, _, loss = step(params, None, tokens, targets)
        assert np.isfinite(float(loss)), loss
        leaf_sum = sum(float(jnp.sum(jnp.abs(x)))
                       for x in jax.tree.leaves(params))
        assert np.isfinite(leaf_sum)


@pytest.mark.heavy
class TestMoE:
    """Mixture-of-experts FFN configs (cfg.n_experts > 0): routing
    correctness against the dense layer, expert-parallel training, and
    decode parity (models/llama.py:_moe_ffn; parallelism row 43 applied to
    the flagship model)."""

    def test_single_expert_matches_dense(self):
        """E=1 top-1 MoE with dropless capacity == the dense SwiGLU model
        with that expert's weights (softmax over one expert is 1.0)."""
        cfg_m = llama.moe_tiny(n_experts=1, k=1)
        cfg_d = llama.tiny()
        pm = llama.init(jax.random.PRNGKey(0), cfg_m)
        pd = llama.init(jax.random.PRNGKey(0), cfg_d)
        # Graft the (single) expert's FFN weights into the dense pytree so
        # both models compute with identical parameters.
        for name in ("w_gate", "w_up", "w_down"):
            pd["layers"][name] = pm["layers"][name][:, 0]
        for name in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"):
            pd["layers"][name] = pm["layers"][name]
        pd["embed"], pd["norm"], pd["head"] = pm["embed"], pm["norm"], pm["head"]
        tokens, _ = _data(cfg_m)
        lm = jax.jit(lambda p, t: llama.apply(cfg_m, p, t))(pm, tokens)
        ld = jax.jit(lambda p, t: llama.apply(cfg_d, p, t))(pd, tokens)
        np.testing.assert_allclose(np.asarray(lm), np.asarray(ld),
                                   atol=1e-4, rtol=1e-4)

    def test_grouped_routing_matches_dense(self):
        """Routing groups (moe_group_size < T) change capacity locality but
        not the math: E=1 top-1 stays dropless per group, so a small group
        size must still reproduce the dense model."""
        base = llama.moe_tiny(n_experts=1, k=1)
        cfg_m = llama.Config(**{**base.__dict__, "moe_group_size": 16})
        cfg_d = llama.tiny()
        pm = llama.init(jax.random.PRNGKey(1), cfg_m)
        pd = llama.init(jax.random.PRNGKey(1), cfg_d)
        for name in ("w_gate", "w_up", "w_down"):
            pd["layers"][name] = pm["layers"][name][:, 0]
        for name in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"):
            pd["layers"][name] = pm["layers"][name]
        pd["embed"], pd["norm"], pd["head"] = pm["embed"], pm["norm"], pm["head"]
        tokens, _ = _data(cfg_m, B=4, L=16)   # T=64 -> 4 groups of 16
        lm = jax.jit(lambda p, t: llama.apply(cfg_m, p, t))(pm, tokens)
        ld = jax.jit(lambda p, t: llama.apply(cfg_d, p, t))(pd, tokens)
        np.testing.assert_allclose(np.asarray(lm), np.asarray(ld),
                                   atol=1e-4, rtol=1e-4)

    def test_aux_loss_near_one_at_init(self):
        """Near-uniform router at init => load-balance aux ~= 1."""
        cfg = llama.moe_tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = _data(cfg)
        _, aux = jax.jit(lambda p, t: llama.apply(cfg, p, t, return_aux=True)
                         )(params, tokens)
        assert 0.5 < float(aux) < 2.0, float(aux)

    @staticmethod
    def _train_losses(cfg, axes, devices, tokens, targets, steps=6):
        """Loss trajectory of the MoE train step on the given mesh axes."""
        mesh = parallel.make_mesh(axes, devices=devices)
        params = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                    mesh, cfg)
        step = llama.make_train_step(cfg, mesh, lr=0.5)
        ls = []
        for _ in range(steps):
            params, _, loss = step(params, None, tokens, targets)
            ls.append(float(loss))
        return ls

    def test_ep_train_matches_dp_only(self, devices):
        """dp x ep expert-parallel step == dp-only step bit-for-policy, and
        loss falls over repeated batches."""
        cfg = llama.moe_tiny()
        tokens, targets = _data(cfg, B=8, L=16)
        ep = self._train_losses(cfg, {"dp": 2, "ep": 4}, devices,
                                tokens, targets)
        dp = self._train_losses(cfg, {"dp": 8}, devices, tokens, targets)
        assert ep[-1] < ep[0] - 0.5, ep
        np.testing.assert_allclose(ep, dp, rtol=1e-4)

    def test_three_axis_dp_ep_tp_matches(self, devices):
        """Full MoE composition: dp x ep x tp (experts over ep, their d_ff
        over tp) trains identically to dp-only."""
        cfg = llama.moe_tiny()
        tokens, targets = _data(cfg, B=8, L=16)
        three = self._train_losses(cfg, {"dp": 2, "ep": 2, "tp": 2}, devices,
                                   tokens, targets)
        dp = self._train_losses(cfg, {"dp": 8}, devices, tokens, targets)
        np.testing.assert_allclose(three, dp, rtol=1e-4)
        assert three[-1] < three[0] - 0.5, three

    def test_expert_sharding_specs(self, devices):
        cfg = llama.moe_tiny()
        mesh = parallel.make_mesh({"dp": 2, "ep": 4}, devices=devices)
        params = llama.shard_params(llama.init(jax.random.PRNGKey(0), cfg),
                                    mesh, cfg)
        spec = params["layers"]["w_gate"].sharding.spec
        assert spec[1] == "ep", spec

    def test_generate_matches_teacher_forced(self):
        """Greedy KV-cache decode == teacher-forced argmax for an MoE model
        (dropless capacity on both paths so routing is identical)."""
        cfg = llama.moe_tiny(n_experts=4, k=2)
        cfg = llama.Config(**{**cfg.__dict__, "capacity_factor": 8.0})
        params = llama.init(jax.random.PRNGKey(3), cfg)
        B, Lp, new = 2, 8, 6
        rng = np.random.RandomState(7)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, Lp)), jnp.int32)
        gen = llama.make_generate_fn(cfg, Lp, new)
        out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
        seq = np.asarray(prompt)
        for i in range(new):
            logits = llama.apply(cfg, params, jnp.asarray(seq))
            nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
            assert np.array_equal(out[:, i], nxt), (i, out[:, i], nxt)
            seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)

    def test_pp_step_rejects_moe(self, devices):
        cfg = llama.moe_tiny()
        mesh = parallel.make_mesh({"pp": 2, "dp": 4}, devices=devices)
        with pytest.raises(NotImplementedError):
            llama.make_pp_train_step(cfg, mesh, n_microbatches=2)
