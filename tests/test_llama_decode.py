"""Decoding for the Llama family (``models/llama_decode``): the cached path
against teacher forcing, the samplers, generation on a mesh, and the decode
step at a position a row."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu import parallel
from torchmpi_tpu.models import llama, llama_decode

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


def _data(cfg, B=4, L=16, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    targets = jnp.asarray(rng.randint(0, cfg.vocab, (B, L)), jnp.int32)
    return tokens, targets


class TestGenerate:
    def test_greedy_matches_teacher_forced(self):
        """KV-cache decode == recomputing the full forward per step: the
        cached path must pick exactly the tokens full-context argmax picks."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=8)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=8, max_new=6)
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
        gen = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=5,
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
        greedy = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=5)
        k1 = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=5,
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
        genk = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=1,
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
        genp = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=5,
                                             temperature=1.5, top_p=1e-6)
        greedy = llama_decode.make_generate_fn(cfg, prompt_len=4, max_new=5)
        np.testing.assert_array_equal(
            np.asarray(genp(params, prompt, jax.random.PRNGKey(3))),
            np.asarray(greedy(params, prompt, jax.random.PRNGKey(4))))

    def test_sampler_validation(self):
        cfg = llama.tiny()
        with pytest.raises(ValueError, match="top_p"):
            llama_decode.make_generate_fn(cfg, 4, 4, top_p=1.5)
        with pytest.raises(ValueError, match="top_k"):
            llama_decode.make_generate_fn(cfg, 4, 4, top_k=-1)
        # Filters without a positive temperature would be silently greedy.
        with pytest.raises(ValueError, match="temperature"):
            llama_decode.make_generate_fn(cfg, 4, 4, top_k=5)

    def test_validation(self):
        cfg = llama.tiny()
        with pytest.raises(ValueError, match=">= 1"):
            llama_decode.make_generate_fn(cfg, prompt_len=0, max_new=4)

    def test_tp_sharded_decode_matches(self, devices):
        """Megatron-sharded params flow through the same compiled generate
        fn — GSPMD partitions the decode matmuls over tp — with identical
        tokens."""
        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        prompt, _ = _data(cfg, B=2, L=8)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=8, max_new=6)
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
        gen = llama_decode.make_generate_fn(cfg, prompt_len=8, max_new=6)
        want = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        mesh = parallel.make_mesh({"dp": 2, "tp": 2},
                                  devices=devices[:4])
        sharded = llama.shard_params(params, mesh, cfg)
        gen_tp = llama_decode.make_generate_fn(cfg, prompt_len=8, max_new=6,
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
            llama_decode.make_generate_fn(cfg_kv1, 8, 4, mesh=mesh)
        # Sampled generation composes with the mesh too (shape + support).
        gen_s = llama_decode.make_generate_fn(
            cfg, prompt_len=8, max_new=5, temperature=0.8, top_k=8, mesh=mesh)
        out = np.asarray(gen_s(sharded, prompt, jax.random.PRNGKey(2)))
        assert out.shape == (4, 5) and out.min() >= 0 and out.max() < cfg.vocab

    @pytest.mark.heavy
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
        real = llama_decode._make_attn_impl

        def spy(cfg_, attn_, mesh_, scale_):
            chosen.append(attn_)
            return real(cfg_, attn_, mesh_, scale_)

        monkeypatch.setattr(llama_decode, "_make_attn_impl", spy)
        gen = llama_decode.make_generate_fn(cfg, prompt_len=Lp, max_new=3)
        got = np.asarray(gen(params, prompt, jax.random.PRNGKey(1)))
        assert "flash" in chosen, chosen
        seq = prompt
        for _ in range(3):
            logits = llama.apply(cfg, params, seq)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(got, np.asarray(seq[:, Lp:]))

    @pytest.mark.heavy
    def test_moe_generate_matches_teacher_forced(self):
        """Greedy KV-cache decode == teacher-forced argmax for an MoE model
        (dropless capacity on both paths so routing is identical)."""
        cfg = llama.moe_tiny(n_experts=4, k=2)
        cfg = llama.Config(**{**cfg.__dict__, "capacity_factor": 8.0})
        params = llama.init(jax.random.PRNGKey(3), cfg)
        B, Lp, new = 2, 8, 6
        rng = np.random.RandomState(7)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, Lp)), jnp.int32)
        gen = llama_decode.make_generate_fn(cfg, Lp, new)
        out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
        seq = np.asarray(prompt)
        for i in range(new):
            logits = llama.apply(cfg, params, jnp.asarray(seq))
            nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
            assert np.array_equal(out[:, i], nxt), (i, out[:, i], nxt)
            seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)


@pytest.mark.parametrize("cfg", [
    llama.tiny(),
    llama.Config(**{**llama.moe_tiny().__dict__, "capacity_factor": 8.0,
                    "qk_norm": True}),
], ids=["dense", "moe-qk-norm"])
def test_the_step_takes_a_position_a_row(cfg):
    """A batch whose rows sit at different positions: row b's logits are
    teacher forcing's at ``pos[b]`` of its own sequence, its key and value
    land at ``pos[b]`` of its own stripe of the cache and nowhere else."""
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens, _ = _data(cfg, B=3, L=12)
    full = llama.apply(cfg, params, tokens)
    pos = jnp.asarray([4, 9, 11])
    cache = llama_decode.init_kv_cache(cfg, 3, 16)
    _, cache = jax.jit(lambda p, c, t: llama_decode._prefill(
        cfg, p, c, t))(params, cache, tokens)
    # what prefill left at and past each row's position must not be read:
    # overwrite it, the step's own write at ``pos[b]`` excepted
    later = jnp.arange(16)[None, :, None, None] >= pos[:, None, None, None]
    spoiled = jax.tree.map(lambda a: jnp.where(later[None], 7.0, a), cache)
    logits, after = jax.jit(lambda p, c, t, at: llama_decode._decode_step(
        cfg, p, c, t, at))(params, spoiled, tokens[jnp.arange(3), pos], pos)
    np.testing.assert_allclose(logits, full[jnp.arange(3), pos],
                               rtol=1e-4, atol=1e-4)
    for name in ("k", "v"):
        changed = np.any(np.asarray(after[name] != spoiled[name]),
                         axis=(0, 3, 4))                    # (B, max_len)
        assert changed.tolist() == (np.arange(16)[None] ==
                                    np.asarray(pos)[:, None]).tolist()
        np.testing.assert_allclose(
            after[name][:, jnp.arange(3), pos], cache[name][:, jnp.arange(3), pos],
            rtol=1e-5, atol=1e-5)
