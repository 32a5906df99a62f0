"""Ask the chip's compiler, without the chip: the kernels of the main path
at their real widths, compiled for a described TPU v5e 2x2 (the machine the
chip tool hands out).  Interpret mode passes shapes Mosaic refuses — a
slice off the tiling, more VMEM than a kernel may use, a kernel GSPMD would
have to partition — so these guard every later PR at no chip time.  A
compile that passes is not a chip run: nothing here says a result is right
or fast.

Refusals are pinned as they are.  The day someone re-tiles the ring kernel
the ``pytest.raises`` below fails, and tells them to move the bound.
"""

import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from torchmpi_tpu.ops import flash_attention
from torchmpi_tpu.ops.flash_attention import flash_bwd_block, flash_fwd_block
from torchmpi_tpu.runtime import topology

H, D = 32, 128          # Llama-3-8B attention heads


@pytest.fixture(scope="module")
def v5e():
    try:
        return topology.topology_devices("v5e-4")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"TPU topology descriptions unavailable: {e!r}")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


V5E_VMEM_BYTES = 128 * 1024 * 1024     # one v5e TensorCore


def _kernel_vmem(compiled):
    """(stated, used) bytes of scoped VMEM of each Mosaic kernel of the
    program, from the custom call's ``backend_config``."""
    size = r'scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"'
    out = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            stated = re.search('"' + size, line)
            used = re.search('"used_' + size, line)
            out.append((int(stated.group(1)), int(used.group(1))))
    return out


@pytest.mark.parametrize("L", [4096, 16384])
def test_flash_fwd_and_grad(v5e, L):
    """``ops.flash_attention`` forward and ``jax.grad``, (1, L, 32, 128)
    bf16 causal, on one chip: one forward kernel, one backward kernel whose
    float32 dq block of the whole (L, 128) sits in VMEM.  The compile is the
    check that its plan fits; what it asks for stays under the chip's."""
    x = _sds((1, L, H, D), jnp.bfloat16, SingleDeviceSharding(v5e[0]))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _kernels(jax.jit(fwd).lower(x, x, x).compile()) == 1
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    assert _kernels(grad) == 2                          # flash_fwd, flash_bwd
    (_, fwd_used), (stated, used) = _kernel_vmem(grad)
    assert fwd_used < 16 * 1024 * 1024                  # the default limit
    assert 2 * L * D * 4 < used <= stated < V5E_VMEM_BYTES


def test_flash_at_heads_of_256(v5e):
    """GLM-4.7-Flash's latent attention, (1, 16384, 20, 256) bf16 causal, keys
    and values both two whole registers a row: the 1024-row blocks as they
    stand, one forward kernel and the ONE backward kernel (not the streaming
    pair), whose float32 dq block of the whole (L, 256) sits in VMEM: 60.8 MB
    asked for, under ``_VMEM_BUDGET`` and the chip's."""
    L, heads, width = 16384, 20, 256
    x = _sds((1, L, heads, width), jnp.bfloat16, SingleDeviceSharding(v5e[0]))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False,
                                       scale=width ** -0.5)
                       .astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    text = grad.as_text()
    assert _kernels(grad) == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "flash_bwd_dq" not in text and "flash_bwd_dkv" not in text
    (_, fwd_used), (stated, used) = _kernel_vmem(grad)
    assert fwd_used < 16 * 1024 * 1024                  # the default limit
    assert 2 * L * width * 4 < used <= stated < V5E_VMEM_BYTES
    assert stated <= 64 * 1024 * 1024


# heads, K/V heads, key and value widths of a (1, 16384, ...) causal call, and
# the parent's (PR 41) own count of scoped VMEM, forward and backward, bytes
TWO_BODIES = [(20, 20, 256, 256, 13844480, 59879424),     # GLM-4.7-Flash
              (48, 8, 128, 128, 9867264, 66142208),       # a Laguna full layer
              (32, 32, 192, 128, 11837440, 55275520)]     # Kimi's latent layer


@pytest.mark.parametrize("heads,kv_heads,width,v_width,fwd_was,bwd_was",
                         TWO_BODIES)
def test_two_bodies_fit_the_vmem_one_body_took(v5e, heads, kv_heads, width,
                                               v_width, fwd_was, bwd_was):
    """``flash_fwd`` and ``flash_bwd`` with a masked and an unmasked body
    each (PR 42), at three cells' shapes in bfloat16: the two bodies stand
    under complementary predicates and the compiler gives their score
    blocks the same VMEM, so neither kernel counts more than the one-body
    kernels did (less: q, k, v and do are no longer held in float32 beside
    their tiles), the backward's streamed part stays inside
    ``_bwd_vmem_bytes`` and the whole inside what ``_bwd_form`` states."""
    from torchmpi_tpu.ops.flash_attention import (_VMEM_BUDGET, _bwd_form,
                                                  _bwd_vmem_bytes)

    L, tile, bf16 = 16384, 1024, jnp.bfloat16
    one = SingleDeviceSharding(v5e[0])
    q = _sds((1, L, heads, width), bf16, one)
    k = _sds((1, L, kv_heads, width), bf16, one)
    v = _sds((1, L, kv_heads, v_width), bf16, one)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False)
                       .astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile()
    text = grad.as_text()
    assert _kernels(grad) == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "flash_bwd_dq" not in text and "flash_bwd_dkv" not in text
    form, vmem = _bwd_form(L, L, width, v_width, tile, tile, bf16, bf16,
                           heads // kv_heads, _VMEM_BUDGET)
    assert form == ("group" if heads > kv_heads else "one")
    streamed = _bwd_vmem_bytes(tile, tile, width, bf16, bf16, v_width)
    resident = vmem - streamed          # dq's block, and a group's dk and dv
    (_, fwd_used), (stated, used) = _kernel_vmem(grad)
    assert fwd_used <= fwd_was < 16 * 1024 * 1024       # the default limit
    assert stated >= vmem and used <= bwd_was <= stated < V5E_VMEM_BYTES
    assert used - resident <= streamed


def test_kda_kernels_at_kimi_linears_widths(v5e, monkeypatch):
    """``ops.kda`` forward and ``jax.grad`` of all five inputs at (1, 16384,
    32, 128), q, k and v bfloat16, the log-decay float32: one kernel each way
    (``kda_fwd``, ``kda_bwd``; no loop over the 256 chunks is left for XLA),
    each inside the 16 MiB of VMEM a kernel gets unasked, reading the (B, L,
    H * D) layout in place: Mosaic takes the tiles' slices, their
    transposed products and the float32 ones at ``HIGHEST``.  With every
    tile's float32 inverse and bfloat16 ``P`` as ``kda_fwd``'s third and
    fourth results and ``kda_bwd``'s operands (each (256, 1, 16, 64, 128): two
    heads' tiles a block, the second stored and read at lane 64) they use
    1.90 and 3.66 MB of it (1.84 and 3.45 without)."""
    from torchmpi_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(v5e[0])
    x = _sds((1, 16384, H, D), jnp.bfloat16, one)
    g = _sds((1, 16384, H, D), jnp.float32, one)
    beta = _sds((1, 16384, H), jnp.float32, one)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.kda(q, k, v, g, beta).astype(jnp.float32))

    fwd = jax.jit(kda.kda).lower(x, x, x, g, beta).compile()
    assert _kernels(fwd) == 1 and "kda_fwd" in fwd.as_text()
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile()
    text = grad.as_text()
    assert _kernels(grad) == 2 and "kda_fwd" in text and "kda_bwd" in text
    assert " while(" not in text
    used = [int(re.search(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', line).group(1)) for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    assert len(used) == 2 and max(used) < 4 * 1024 * 1024
    fwd_line, bwd_line = (
        next(line for line in text.splitlines()
             if f"%{name}" in line and "custom-call(" in line)
        for name in ("kda_fwd", "kda_bwd"))
    for kept in ("f32[256,1,16,64,128]", "bf16[256,1,16,64,128]"):
        assert kept in fwd_line.split("custom-call(")[0]        # a result
        assert kept in bwd_line.split("operand_layout_constraints")[1]


def _ring_blocks(one, Lc):
    x = _sds((H, Lc, D), jnp.bfloat16, one)
    row = _sds((H, Lc, 1), jnp.float32, one)

    def fwd(q, k, v):
        return flash_fwd_block(q, k, v, causal=True, interpret=False,
                               out_dtype=jnp.float32)

    def bwd(q, k, v, do, lse, delta):
        return flash_bwd_block(q, k, v, do, lse, delta, causal=True,
                               interpret=False, out_dtype=jnp.float32)

    return (jax.jit(fwd).lower(x, x, x).compile(),
            jax.jit(bwd).lower(x, x, x, x, row, row).compile())


def test_ring_flash_blocks_f32_out(v5e):
    """The ring's per-chunk kernels at L=16384 over sp=4: local Q against
    one circulating K/V chunk of 4096, partial outputs carried in f32."""
    fwd, bwd = _ring_blocks(SingleDeviceSharding(v5e[0]), 16384 // 4)
    assert _kernels(fwd) == 1
    assert _kernels(bwd) == 1                           # flash_bwd


@pytest.mark.parametrize("Lc,kernels", [(65536, 1), (131072, 2)])
def test_flash_bwd_form_at_long_chunks(v5e, Lc, kernels):
    """A local chunk of 65,536 rows still takes the one backward kernel
    (its dq block is 64 MiB in both buffers) and the compiler takes its
    VMEM plan; at 131,072 the two streaming kernels compile instead."""
    _, bwd = _ring_blocks(SingleDeviceSharding(v5e[0]), Lc)
    assert _kernels(bwd) == kernels
    assert all(used <= stated < V5E_VMEM_BYTES
               for stated, used in _kernel_vmem(bwd))


def _ring_allreduce(n):
    fn, args = topology._build_pallas_ring("v5e-4", "float32", n)
    return jax.jit(fn).lower(*args).compile()


def test_ring_allreduce_compiles_at_64k_elements(v5e):
    assert _kernels(_ring_allreduce(1 << 16)) == 1


def test_ring_allreduce_vmem_bound_pinned(v5e):
    """The ring keeps the whole payload in VMEM, so 4,194,304 elements
    (16 MB of float32, a sixth of a ResNet-50 gradient) do not compile:
    40 MB of scoped VMEM against a 16 MB limit.  Pinned, not repaired
    (ROADMAP queue 3 item 6): re-tile the kernel and move this bound."""
    with pytest.raises(Exception, match="(?i)vmem"):
        _ring_allreduce(1 << 22)


def test_llama_flash_step_on_dp_tp(v5e, monkeypatch):
    """``make_train_step(attn="flash")`` on dp=2 x tp=2: the Mosaic kernel
    must reach the compiler inside a shard_map (under GSPMD it is refused:
    "Mosaic kernels cannot be automatically partitioned").  The step reads
    the running backend to choose interpret mode, so the test answers for
    it; no option of the program does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = topology._build_llama_dp_tp("v5e-4", attn="flash")
    compiled = jax.jit(fn).lower(*args).compile()
    assert _kernels(compiled) > 0
    # `tiny`'s 4 heads over 2 K/V heads, split over tp at BOTH counts: a
    # device's kernels take its 2 query heads and its 1 K/V head, which the
    # two share by the kernels' index maps, and `flash_bwd` gives dk and dv
    # at that one head (no repeat stands in the shard_map's body).
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "flash_" in line]
    heads = lambda line: [int(n) for n in re.findall(
        r"(?:bf16|f32)\[(\d+),\d+,16\]",
        line.split("operand_layout_constraints")[1])]
    assert calls and all(heads(line)[:3] == [2 * heads(line)[1],
                                            heads(line)[1], heads(line)[2]]
                         for line in calls)
    # The chunked head under GSPMD (head columns over tp, rows over dp): the
    # program with a checkpointed chunk held 11 all-reduces of 255,364 bytes,
    # four in a chunk (max and target logit, then max and sum again in the
    # replay) and dh's after the scan.  Now three in a chunk (max, sum, and
    # the target logit with the chunk's dh: the same bytes a step), and dW is
    # still summed over dp once, after the scan, with the other gradients.
    # The text counts an instruction once: `tiny`'s two layers are inlined,
    # so each layer's four activation all-reduces over tp (16,384 bytes,
    # attention and FFN, forward and backward) stands there itself, where a
    # scan's body stood once whatever its trips (9 instructions, 246,916
    # bytes), and the layers' dp sums ride with the embedding's.
    text = compiled.as_text()
    stats = topology.hlo_collective_stats(text)
    assert set(stats["counts"]) == {"all-reduce:f32"}
    assert stats["total"] <= 12
    assert sum(stats["operand_bytes"].values()) <= 386_692
    in_chunk = [line for line in text.splitlines()
                if " all-reduce(" in line and "head_loss" in line]
    assert len(in_chunk) == 3 and all("while/body" in line for line in in_chunk)


def test_olmoe_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `olmoe-1b-7b-l4096` step on one chip: OLMoE-1B-7B at
    its published widths, 2 of 16 layers, inlined (`llama.apply` scans no
    stack this shallow), 4 x 4096 tokens, flash, remat "dots", AdamW with
    bfloat16 moments, weights and state donated.  It fits the chip, and
    holds, for each layer, the two flash kernels and the grouped matmuls of
    the sorted dispatch: `gmm` for gate, up and down forward and the three
    gradients of the rows (6: the 9 products a layer requires and none again,
    remat "dots" keeping the gate and up products by their names and nothing
    reading the down product's), `tgmm` for the three gradients of the
    weights.  What the scan cost is not there: no
    layer's expert weights copied out of the stack by a `dynamic-slice`, no
    gradient written into it by a `dynamic-update-slice` (22.4 ms of a
    316.65 ms step and 3.65 GB of the plan: PERF_LEDGER.jsonl, PR 28)."""
    import dataclasses
    import re

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(llama.olmoe_1b_7b(), n_layers=2)
    assert cfg.n_layers <= llama._INLINE_MAX_LAYERS
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 1_045_186_560
    optimizer = optax.adamw(4e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    state = jax.eval_shape(optimizer.init, jax.tree.map(
        lambda a: _sds(a.shape, jnp.bfloat16, one), params))
    mesh = Mesh([v5e[0]], ("dp",))
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat="dots", loss_chunk=512)
    tokens = _sds((4, 4096), jnp.int32, one)
    compiled = step.lower(place(params), place(state), tokens, tokens).compile()
    text = compiled.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    # (inlined, a forward kernel's scope reads `jvp(moe.experts)/jit(gmm)`)
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert (named("flash_fwd"), named("flash_bwd")) == (2, 2)
    assert named(r"moe\.experts\)?/jit\(gmm\)") == 12
    assert named(r"moe\.experts\)?/jit\(tgmm\)") == 6
    assert len(kernels) == 22
    assert not named(r"rematted_computation.*jit\(t?gmm\)")
    # No slice of the stacked expert weights, (2, 64, 2048, 1024) and its
    # transpose, cut or written at an index the program computes.
    expert = re.compile(r"bf16\[(2,)?64,(2048,1024|1024,2048)\]")
    assert not [line for line in text
                if re.search(r"dynamic-(update-)?slice", line)
                and expert.search(line)]
    # The head: three products over the vocabulary in one scan body (logits,
    # dh, dW), and no replay of `h_c @ head` in a backward scan.
    head = [line for line in text
            if "head_loss" in line and " convolution(" in line]
    assert len(head) == 3 and not any("rematted" in line for line in head)
    assert all("jvp(head_loss)/while/body" in line for line in head)
    assert sum('head_loss)/while"' in line and " while(" in line
               for line in text) == 1
    assert sum("bf16[4,512,50304]" in line.split(" convolution(")[0]
               for line in head) == 1
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both moments donated: all but the tokens and a few norms
    assert m.argument_size_in_bytes - m.alias_size_in_bytes < 1e6
    assert m.alias_size_in_bytes > 3 * 2 * 1_045_000_000
    # The plan: 12.04 GB, 6.27 of weights and moments (3 x 2 bytes x 1.045 G)
    # and 5.77 of temporaries.  The gate and up products kept for the backward
    # pass are 2 layers x 2 x (8 x 16,384 rows) x 1024 x 2 bytes = 1.07 GB,
    # yet the plan that replayed them held 11.83 (temporaries 5.56): its peak
    # lies in the last layer's backward pass, where the replayed pair stood
    # too, so only the first layer's pair, less what it displaces, is new.
    assert 9e9 < held < 12.5e9


def test_olmoe_step_on_dp_tp_takes_the_compilers_grouped_matmul(monkeypatch):
    """On more than one device the sorted dispatch leaves the grouped matmul
    to `lax.ragged_dot`, which the compiler partitions under GSPMD (its own
    Mosaic kernel, named `ragged-dot-none`); megablox's, a Mosaic kernel of
    ours, it would refuse to.  One layer at published widths on dp=2 x tp=2:
    the 9 products the layer requires, the gate and up products kept through
    remat "dots" by the names they carry in this form too."""
    import dataclasses

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(llama.olmoe_1b_7b(), n_layers=1)
    mesh = topology.topology_mesh("v5e-4", {"dp": 2, "tp": 2})
    args = topology._llama_arg_structs(cfg, mesh, llama.param_specs, 4, 4096)
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="dots",
                                 loss_chunk=512)
    text = jax.jit(lambda p, t, y: step(p, None, t, y)).lower(
        *args).compile().as_text()
    assert text.count('op_name="ragged-dot-none"') == 9
    assert "jit(gmm)" not in text


def test_kimi_linear_step_on_dp_tp_runs_each_devices_kernels(monkeypatch):
    """On more than one device a KDA layer between its projections (the way
    in, the recurrence, the way out) runs in ONE ``shard_map`` over the batch
    and the heads (``llama._kda_sharded``), as flash does: its kernels are
    Mosaic's, and the compiler refuses to partition one
    (``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned``; bare under GSPMD this step does not lower).  Kimi Linear's
    first four layers at published widths (KDA, KDA, KDA, MLA; a share of the
    experts) on dp=2 x tp=2: each device runs ``kda_fwd`` and ``kda_bwd``
    once a KDA layer on its own row of the batch and its 16 of 32 heads,
    beside them ``kda_pre`` and ``kda_post`` twice (``"full"`` forms them
    again from their inputs) and ``kda_pre_bwd`` and ``kda_post_bwd`` once,
    and the latent layer's two flash kernels on its 16 heads."""
    import dataclasses

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    published = llama.kimi_linear_48b_a3b()
    cfg = dataclasses.replace(
        published, n_layers=4, layer_kinds=published.layer_kinds[:4],
        experts_held=(0, 8), vocab=20480)
    assert [m for m, _ in cfg.layer_kinds] == ["kda", "kda", "kda", "mla"]
    mesh = topology.topology_mesh("v5e-4", {"dp": 2, "tp": 2})
    args = topology._llama_arg_structs(cfg, mesh, llama.param_specs, 2, 4096)

    def lowered():
        step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                     loss_chunk=512)
        return jax.jit(lambda p, t, y: step(p, None, t, y)).lower(*args)

    text = lowered().compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert (named("kda_fwd"), named("kda_bwd")) == (3, 3)
    assert (named(r"kda_pre(?!_bwd)"), named(r"kda_post(?!_bwd)")) == (6, 6)
    assert (named("kda_pre_bwd"), named("kda_post_bwd")) == (3, 3)
    assert (named("flash_fwd"), named("flash_bwd")) == (1, 1)
    assert "jit(gmm)" not in text
    # a device's o and states: its one row, 4096 tokens in 64 chunks, 16 heads
    fwd = next(line for line in kernels if "kda_fwd" in line)
    assert "[1,4096,2048]" in fwd and "[64,1,16,128,128]" in fwd
    # the way in's four results on the same rows and heads
    pre = next(line for line in kernels if "kda_pre" in line
               and "kda_pre_bwd" not in line)
    assert pre.split(" custom-call(")[0].count("[1,4096,2048]") == 4
    import functools

    from torchmpi_tpu.ops.kda_mixer import kda_mixer
    monkeypatch.setattr(llama, "_kda_sharded", lambda mesh, heads, eps:
                        functools.partial(kda_mixer, eps=eps))
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lowered()


def test_ouro_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `ouro-2.6b-b2-l4096` step on one chip: Ouro-2.6B at
    its published widths, 8 of 48 layers run four times (32 layer
    applications: the layer scan inside, the recurrent steps inlined round
    it), 2 x 4096 tokens, flash, the configuration file's remat (three steps
    `"full"`, one `"dots"`), the four heads through one chunked call, AdamW
    with float32 moments, weights and state donated.  `benchmark/sizing.py`
    knows no function for this runner, so this is the cell's plan: 16.80 GB
    under the chip's 15.75 GiB (16.91 GB), where the plan that replayed the
    forward kernels held 15.07 (15.16 with no barrier round the scanned
    layers' checkpoints).  The o and lse of the 24 layer applications under
    `"full"` are 24 x (33.6 + 0.5) MB = 0.82 GB, and the compiler's own peak
    (`peak_memory_in_bytes`) rose by just that, 12.60 to 13.42 GB; arguments
    plus temporaries, the sum the cell reports, count it twice.  `"dots"` at
    every step is refused."""
    import dataclasses
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "ouro-2.6b.json")) as fh:
        run = json.load(fh)["run"]
    cfg = dataclasses.replace(llama.ouro_2_6b(), n_layers=8)
    assert cfg.n_layers > llama._INLINE_MAX_LAYERS and cfg.ut_steps == 4
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 612_438_017
    adamw = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def update(grads, state, params):       # moments float32, as the runner
        updates, state = adamw.update(f32(grads), state, f32(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    optimizer = optax.GradientTransformation(lambda p: adamw.init(f32(p)),
                                             update)
    state = jax.eval_shape(optimizer.init, params)
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((2, 4096), jnp.int32, one)

    def compiled(remat):
        step = llama.make_train_step(cfg, mesh, attn="flash",
                                     optimizer=optimizer, remat=remat,
                                     loss_chunk=run["loss_chunk"])
        return step.lower(place(params), place(state), tokens, tokens).compile()

    program = compiled(run["remat"])
    text = program.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    # One forward and one backward kernel in each recurrent step's scan body
    # and no other: a step that recomputes its layers ("full") keeps the
    # forward kernel's o and lse as a "dots" step does, and still recomputes
    # the rest (the SwiGLU's products under `rematted_computation/ffn`).
    assert run["remat"] == ["full", "full", "full", "dots"]
    assert sum("flash_fwd" in line for line in kernels) == 4
    assert sum("flash_bwd" in line for line in kernels) == 4
    assert len(kernels) == 8
    assert not any("rematted_computation" in line for line in kernels)
    assert sum("rematted_computation/ffn" in line and " convolution(" in line
               for line in text) >= 3
    # The head: three products over the vocabulary in one scan body, on the
    # 4 x 2 rows of all the recurrent steps' states, none replayed.
    head = [line for line in text
            if "head_loss" in line and " convolution(" in line]
    assert len(head) == 3 and not any("rematted" in line for line in head)
    assert sum("bf16[8,512,49152]" in line.split(" convolution(")[0]
               for line in head) == 1
    m = program.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both float32 moments donated: 10 bytes a parameter
    assert m.alias_size_in_bytes > 10 * 612_000_000
    assert 0.25 * 16e9 < held < 15.75 * 2**30
    with pytest.raises(Exception, match="hbm"):
        compiled("dots")


def test_kimi_linear_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `kimi-linear-48b-a3b-l16k` step on one chip:
    Kimi-Linear-48B-A3B at its published widths, the first 5 of 27 layers (KDA
    and a dense FFN; KDA, KDA, MLA, KDA with experts: four runs, inlined), 8
    of 256 routed experts a layer held here beside the shared one, 20,480
    rows of the vocabulary, 1 x 16,384 tokens, flash with keys of 192 and
    values of 128, the configuration file's remat, AdamW with float32
    moments, weights and state donated.  It fits the chip: the compiler's
    own peak is 13.41 GB of 16.91 (15.75 GiB) and the sum the cell reports
    13.89 GB.  Two flash kernels for the one MLA layer and, for each of the
    four expert layers, the grouped matmuls of one pass of the held experts'
    loops: `gmm` forward (3), for the rows' gradients (3) and, the backward
    loop forming what it does not keep, gate and up again (2), `tgmm` for
    the weights' gradients (3); the forward loop that `"full"` replays is
    dead there and gone.  A KDA layer's recurrence is two more, `kda_fwd`
    and `kda_bwd`, the 256 chunks a grid axis each runs in turn: no loop is
    left under `kda`, and the forward kernel that `"full"` would replay is
    dead, its output, states, inverses and `P` kept.  The layer's passes
    round the recurrence are six more (`ops/kda_mixer.py`): `kda_pre` and
    `kda_post` forward and, kept by their inputs alone, formed again under
    `"full"`,
    `kda_pre_bwd` and `kda_post_bwd` once; they stand under `attn`, not under
    `kda`."""
    import dataclasses
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.kimi_linear_48b_a3b()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=20480)
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 8, 20480)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 2, 1, 1]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 602_450_816
    adamw = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9, b2=0.95,
                        weight_decay=0.1)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def update(grads, state, params):       # moments float32, as the runner
        updates, state = adamw.update(f32(grads), state, f32(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    optimizer = optax.GradientTransformation(lambda p: adamw.init(f32(p)),
                                             update)
    state = jax.eval_shape(optimizer.init, params)
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((1, 16384), jnp.int32, one)
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"])
    program = step.lower(place(params), place(state), tokens,
                         tokens).compile()
    text = program.as_text().splitlines()
    kernels = [line for line in text
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what: sum(bool(re.search(what, line)) for line in kernels)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd")) == (1, 1)
    assert all("/mla/" in line for line in kernels if "flash_" in line)
    assert named(r"jit\(gmm\)") == 4 * 8 and named(r"jit\(tgmm\)") == 4 * 3
    assert (named("kda_fwd"), named("kda_bwd")) == (4, 4)
    assert (named(r"kda_pre(?!_bwd)"), named(r"kda_post(?!_bwd)")) == (8, 8)
    assert (named("kda_pre_bwd"), named("kda_post_bwd")) == (4, 4)
    recurrence = lambda line: "kda_fwd" in line or "kda_bwd" in line
    assert all(("/kda/" in line) == recurrence(line)
               and re.search(r"[/(]attn[/)]", line)
               for line in kernels if "kda_" in line)
    assert len(kernels) == 46 + 2 * 4 + 6 * 4
    # what "full" forms again: the way in and the way out, never a
    # recurrence or a flash kernel
    assert not any("rematted_computation" in line for line in kernels
                   if "flash_" in line or recurrence(line))
    assert sum("rematted_computation" in line for line in kernels
               if "kda_" in line) == 2 * 4
    assert not any(" while(" in line and "/kda/" in line for line in text)
    m = program.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # weights and both float32 moments donated: 10 bytes a parameter
    assert m.alias_size_in_bytes > 10 * 602_000_000
    # That it compiled is the check that it fits 15.75 GiB.  The compiler's
    # own peak is 13.41 GB and arguments plus temporaries, the sum the cell
    # reports as `hbm_program_gb`, 13.89 GB: a record of the plan, not a
    # limit of the chip.  Before the four KDA layers kept their tiles'
    # inverses and `P` (`ops.kda.residual_bytes`: 4 x (0.134 + 0.067) = 0.81
    # GB from forward to backward) they read 12.94 and 13.69; the sum grew by
    # 0.20 and not by 0.81 because its temporaries are the highest point of a
    # heap that the compiler packs anew, not a sum of what is kept; the peak
    # by 0.47.
    from torchmpi_tpu.ops import kda

    kept = kda.residual_bytes(1, 16384, cfg.kda_heads, cfg.kda_head_dim,
                              jnp.bfloat16)
    assert 4 * (kept["kda_inverse"] + kept["kda_p"]) == 6 * 2**27
    assert 8e9 < m.peak_memory_in_bytes < 14e9
    assert m.peak_memory_in_bytes < held < 14e9


def test_glm_flash_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `glm-4.7-flash-l16k` step on one chip: GLM-4.7-Flash at
    its published widths, the first 5 of 47 layers (a dense FFN, then four
    with experts: two runs, inlined) and the multi-token-prediction module, 8
    of 64 routed experts a layer held here beside the shared one, 19,360 rows
    of the vocabulary (151.25 tiles of 128), 1 x 16,384 tokens, flash at
    heads of 256, the configuration file's remat, AdamW with float32 moments,
    weights and state donated.  The compiler's own peak is 13.05 GB of 16.91
    (15.75 GiB) since PR 41 (15.39 before it, where `"dots"` was refused by 58
    MB).  Two flash kernels for each of the six latent layers,
    the module's under `mtp`, none replayed, and eleven grouped matmuls for
    each of the five expert layers, as in the Kimi Linear step."""
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-4.7-flash.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.glm_4_7_flash()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=19360)
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"], file["num_nextn_predict_layers"]) == (
        5, 8, 19360, 1)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 4]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 706_518_848
    adamw = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9, b2=0.95,
                        weight_decay=0.1)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def update(grads, state, params):       # moments float32, as the runner
        updates, state = adamw.update(f32(grads), state, f32(params))
        return jax.tree.map(lambda u, p: u.astype(p.dtype), updates,
                            params), state

    optimizer = optax.GradientTransformation(lambda p: adamw.init(f32(p)),
                                             update)
    state = jax.eval_shape(optimizer.init, params)
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((1, 16384), jnp.int32, one)
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"])
    program = step.lower(place(params), place(state), tokens,
                         tokens).compile()
    kernels = [line for line in program.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (6, 6)
    assert all("/mla/" in line for line in kernels if "flash_" in line)
    module = [line for line in kernels if re.search(r"[(/]mtp[)/]", line)]
    assert (named("flash_fwd", module), named("flash_bwd", module)) == (1, 1)
    assert len(kernels) == 6 * 2 + 5 * 11 and len(module) == 2 + 11
    peak = program.memory_analysis().peak_memory_in_bytes
    # 15.39 GB until PR 41, whose rotation (`llama._rotate_pairs`) leaves
    # the compiler no sequence-on-the-lanes copies of q and k to keep.
    assert 12.0e9 < peak < 14.0e9


@pytest.mark.parametrize("tile", [256, 512, 1024])
def test_windowed_flash_at_laguna_widths(v5e, tile):
    """A Laguna-S-2.1 sliding layer's attention, q (1, 16384, 72, 128) with
    K and V at the layer's 8 heads, bf16 over a 512-key window, at the tile
    the program chooses (512) and at its neighbours: one forward kernel and
    the ONE backward kernel, whose grids walk the band's blocks alone (3, 2
    and 2 of them a Q block where the causal grid has 64, 32 and 16), inside
    the chip's VMEM with the group's float32 dk and dv of the whole (L, 128)
    beside dq's (three blocks in both buffers).  K and V are read where they
    are, nine query heads a head: the same two kernels as with K and V
    repeated to 72 heads first (the form before PR 41), and a compiler's
    peak of 2.99 GB where that form's is 4.06 (compiled here at PR 41, every
    tile)."""
    from torchmpi_tpu.ops.flash_attention import blocks_met, operand_plan

    L, heads, kv_heads, width, window = 16384, 72, 8, 128, 512
    assert blocks_met(L, window)["tile"] == 512
    assert operand_plan(1, L, heads, kv_heads, width, width,
                        window=window)["dkv_in_kernel"]
    one = SingleDeviceSharding(v5e[0])
    x = _sds((1, L, heads, width), jnp.bfloat16, one)
    kv = _sds((1, L, kv_heads, width), jnp.bfloat16, one)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False,
                                       window=window, block_q=tile,
                                       block_k=tile).astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, kv, kv).compile()
    text = grad.as_text()
    assert _kernels(grad) == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "flash_bwd_dq" not in text and "flash_bwd_dkv" not in text
    (_, fwd_used), (stated, used) = _kernel_vmem(grad)
    assert fwd_used < 16 * 1024 * 1024                  # the default limit
    assert 3 * 2 * L * width * 4 < used <= stated < V5E_VMEM_BYTES
    assert grad.memory_analysis().peak_memory_in_bytes < 0.8 * 4.06e9


def test_laguna_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `laguna-s-2.1-l16k` step on one chip: Laguna-S-2.1 at
    its published widths, the first 5 of 48 layers (a full layer with the
    dense FFN, three window layers and a full one with experts: three runs,
    inlined), 8 of 256 routed experts a layer held here beside the shared
    one, 12,544 rows of the vocabulary, 1 x 16,384 tokens, the configuration
    file's remat, AdamW with bfloat16 moments, weights and state donated.
    The compiler's own peak is 14.09 GB of 16.91 (15.75 GiB); with float32
    moments it refuses the step by 437 MB (my compile of PR 40: 2.5 GB of the
    plan is the five layers' log-sum-exp columns padded to 128 lanes).  Two
    flash kernels a layer, the window layers' under `swa`, none replayed."""
    import json
    import os

    import optax
    from jax.sharding import Mesh

    from torchmpi_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-s-2.1.json")) as fh:
        file = json.load(fh)
    run = file["run"]
    published = llama.laguna_s_2_1()
    cfg = dataclasses.replace(
        published, n_layers=5, layer_kinds=published.layer_kinds[:5],
        experts_held=(0, 8), vocab=12544)
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 8, 12544)
    assert [n for *_, n in llama.layer_runs(cfg)] == [1, 3, 1]
    one = SingleDeviceSharding(v5e[0])
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one), tree)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 811_017_216
    assert run["optimizer"]["moments_dtype"] == "bfloat16"
    optimizer = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9,
                            b2=0.95, weight_decay=0.1)
    state = jax.eval_shape(optimizer.init, params)      # bfloat16, as they
    mesh = Mesh([v5e[0]], ("dp",))
    tokens = _sds((1, 16384), jnp.int32, one)
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"])
    program = step.lower(place(params), place(state), tokens,
                         tokens).compile()
    kernels = [line for line in program.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (5, 5)
    window = [line for line in kernels if "/swa/" in line]
    assert (named("flash_fwd", window), named("flash_bwd", window)) == (3, 3)
    assert len(window) == 6
    assert len(kernels) == 5 * 2 + 4 * 11               # as before PR 41
    for line in kernels:
        if "flash_" in line:      # q's 72 or 48 heads, K and V at their 8
            heads = [int(n) for n in re.findall(
                r"bf16\[(\d+),16384,128\]",
                line.split("operand_layout_constraints")[1])]
            assert heads[0] in (48, 72) and heads[1:3] == [8, 8]
    # Under the 14.09 GB of the step with K and V repeated and the sliced
    # rotation (compiled here at PR 40 and again at PR 41): 13.06 GB.
    peak = program.memory_analysis().peak_memory_in_bytes
    assert 12.0e9 < peak < 14.0e9


def test_mellum2_ep4_adamw_step_at_published_widths(v5e, monkeypatch):
    """The benchmark's `mellum2-12b-a2.5b-ep4-l8k` step on the four chips of
    a described v5e 2x2, a mesh of ``ep`` = 4: Mellum2-12B-A2.5B at its
    published widths, one whole period of 28 layers (three window layers and
    a full one, experts in each), all 64 experts, 16 a chip, the whole
    vocabulary on every chip, 8 x 8,192 tokens, two rows a chip, the
    configuration file's remat, AdamW with bfloat16 moments, weights and state
    donated.  The first program of this file that is one program across four
    chips.  The compiler's own peak a chip is 12.92 GB of 16.91 (15.75 GiB)
    with a first pass of the whole uniform share a peer and overflow passes
    a quarter of it (my compile of PR 46; 13.53 GB when every pass was the
    share, and then with float32 moments 17.1 GB and refused; at half the
    share a pass 12.02 GB with bfloat16 moments and 15.60 with float32, my
    compiles of PR 44; the latter ran on the chip, its steps moving by whole
    passes with the routing): two rows a chip fit, the moments' type is the
    file's choice.  The flash kernels stand in their
    ``shard_map`` over ``ep`` (the batch's rows), the experts' grouped
    matmuls are Mosaic kernels too (every axis of the mesh is the expert
    layer's ``shard_map``'s), and the exchange is ``all-to-all``s by name."""
    import json
    import os

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchmpi_tpu.models import llama
    from torchmpi_tpu.models._common import mesh_spec

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as fh:
        file = json.load(fh)
    with open(os.path.join(root, "benchmark", "traffic",
                           "ep4-l8k.json")) as fh:
        mix = json.load(fh)
    run = file["run"]
    published = llama.mellum2_12b_a2_5b()
    cfg = dataclasses.replace(published, n_layers=4,
                              layer_kinds=published.layer_kinds[:4])
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (4, 64, 98304)
    assert [n for *_, n in llama.layer_runs(cfg)] == [3, 1]
    assert mix["mesh"] == {"ep": 4} and (mix["batch"], mix["seq_len"]) == (
        8, 8192)
    mesh = Mesh(np.array(v5e[:4]), ("ep",))
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg,
                                               dtype=jnp.bfloat16))
    assert sum(a.size for a in jax.tree.leaves(params)) == 2_123_976_960
    placed = jax.tree.map(
        lambda a, s: _sds(a.shape, a.dtype, NamedSharding(
            mesh, mesh_spec(s, mesh, a.shape))), params,
        llama.param_specs(cfg))
    a_chip = sum(int(np.prod(a.sharding.shard_shape(a.shape)))
                 for a in jax.tree.leaves(placed))
    assert a_chip == 934_891_776                # 16 of 64 experts a layer
    # AdamW as `benchmark/runners/step_tokens_adamw.py:_optimizer` builds it:
    # both moments in the file's type, whatever the weights'.
    moments = jnp.dtype(run["optimizer"]["moments_dtype"])
    assert moments == jnp.bfloat16
    adamw = optax.adamw(run["optimizer"]["learning_rate"], b1=0.9, b2=0.95,
                        weight_decay=0.1)
    cast = lambda tree: jax.tree.map(lambda a: a.astype(moments), tree)

    def update(grads, state, p):
        updates, state = adamw.update(cast(grads), state, cast(p))
        return jax.tree.map(lambda u, a: u.astype(a.dtype), updates,
                            p), state

    optimizer = optax.GradientTransformation(
        lambda p: adamw.init(cast(p)), update)
    like = iter(jax.tree.leaves(placed) * 2)
    state = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, next(like).sharding if a.ndim
                       else NamedSharding(mesh, P())),
        jax.eval_shape(optimizer.init, params))
    assert llama.batch_spec(cfg, mesh) == P("ep", None)
    tokens = _sds((mix["batch"], mix["seq_len"]), jnp.int32,
                  NamedSharding(mesh, llama.batch_spec(cfg, mesh)))
    step = llama.make_train_step(cfg, mesh, attn="flash", optimizer=optimizer,
                                 remat=run["remat"],
                                 loss_chunk=run["loss_chunk"],
                                 with_delivered=True)
    program = step.lower(placed, state, tokens, tokens).compile()
    text = program.as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    named = lambda what, lines=kernels: sum(
        bool(re.search(what, line)) for line in lines)
    assert run["remat"] == "full"
    assert (named("flash_fwd"), named("flash_bwd[^_]")) == (4, 4)
    window = [line for line in kernels if "/swa/" in line]
    assert (named("flash_fwd", window), named("flash_bwd", window)) == (3, 3)
    # A layer's grouped matmuls: 3 forward, and gate and up again with the
    # three products' two gradients each backward, in the first pass's body
    # and again in the overflow passes'.
    assert len(kernels) == 4 * 2 + 4 * 2 * 11
    sizes = (llama.ep_pass_rows(cfg, 2 * 8192, 4),
             llama.ep_overflow_rows(cfg, 2 * 8192, 4))
    assert sizes == (32768, 8192)               # the share, a quarter of it
    exchanged = [line for line in text.splitlines()
                 if re.search(r"= \S+ all-to-all", line)]
    # at each size, a layer's forward pass sends rows and weights out and
    # results back, its backward pass rows, weights and cotangents out and
    # two cotangents back; the plan's counts, forward and replayed; and one
    # after the forward loop for the senders' counts of the rows they filled
    assert len(exchanged) == 4 * (2 * (3 + 5) + 2 + 1) and all(
        "moe.exchange" in line for line in exchanged)
    # every (4, rows, ...) block of rows is bfloat16: 604 MB a first pass
    for rows in sizes:
        assert sum(f"bf16[4,{rows},2304]" in line
                   for line in exchanged) == 4 * 5
    peak = program.memory_analysis().peak_memory_in_bytes
    assert 12.4e9 < peak < 13.5e9
    assert peak > 0.25 * 16e9                   # the benchmark's floor
