"""Ask the chip's compiler, without the chip: the kernels of the main path
at their real widths, compiled for a described TPU v5e 2x2 (the machine the
chip tool hands out).  Interpret mode passes shapes Mosaic refuses — a
slice off the tiling, more VMEM than a kernel may use, a kernel GSPMD would
have to partition — so these guard every later PR at no chip time.  A
compile that passes is not a chip run: nothing here says a result is right
or fast.

The benchmark's steps of six model families at their published widths, the
long compiles, are in ``tests/test_aot_steps_*.py``, three a file: the driver
hands a worker a FILE at a time, and a step holds four to five cores for
minutes while the chip's compiler works, so they are queued last
(``tests/conftest.py``) and fill the cores the run's last workers leave.

Refusals are pinned as they are.  The day someone re-tiles the ring kernel
the ``pytest.raises`` below fails, and tells them to move the bound.
"""

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


@pytest.mark.parametrize("N,R,D,held", [(65536, 8192, 2304, 16),
                                        (16384, 32768, 2048, 8)],
                         ids=["mellum2", "glm"])
def test_scatter_add_rows_at_the_cells_passes(v5e, N, R, D, held):
    """The passes' float32 scatter-add alone, 16 calls in a loop that carries
    the sums as the expert layer's loops do, at the Mellum2 cell's pass
    (8,192 rows of 2,304 into 65,536 gathered tokens' sums, 16 held experts)
    and at the GLM cell's (32,768 rows of 2,048 into 16,384 tokens', 8): one
    kernel under its own name, the sums a row a slab (what Mosaic will slice
    a row of: of (N, D) it refuses, "Slice shape along dimension 0 must be
    aligned to tiling (8), but is 1"), added to in place (no copy of them in
    the loop), the tile the width's (256 rows), inside the VMEM it states."""
    from jax import lax

    from torchmpi_tpu.ops.scatter_add_rows import row_tile, scatter_add_rows

    one = SingleDeviceSharding(v5e[0])

    def passes(sums, index, rows, kept):
        return lax.fori_loop(0, 16, lambda i, s: scatter_add_rows(
            s, index[i], rows, kept[i]), sums)

    args = (_sds((N, 1, D), jnp.float32, one), _sds((16, R), jnp.int32, one),
            _sds((R, D), jnp.bfloat16, one), _sds((16, held), jnp.int32, one))
    program = jax.jit(passes, donate_argnums=0).lower(*args).compile()
    text = program.as_text()
    assert _kernels(program) == 1 and "scatter_add_rows" in text
    assert not re.search(rf"f32\[{N},1,{D}\]\S* (copy|scatter)\(", text)
    (stated, used), = _kernel_vmem(program)
    tile = row_tile(D)
    assert tile == 256
    assert 4 * tile * D * 4 <= used <= stated < V5E_VMEM_BYTES // 2
