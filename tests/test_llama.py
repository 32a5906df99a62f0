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

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


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
        l_zz, g_zz = jax.jit(jax.value_and_grad(lf))(sharded,
                                                     (tokens, targets))
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
        l_zz, g_zz = jax.jit(jax.value_and_grad(
            llama.make_loss_fn(cfg, mesh=mesh, attn="ring-zigzag")))(
            sharded, (tokens, targets))
        np.testing.assert_allclose(float(l_zz), float(l_full), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(g_zz), jax.tree.leaves(g_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=2e-4)

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
