"""Runner `step_tokens_ssm`: the `step_tokens` protocol (a decoder language
model trained through `mpi.start()` -> `parallel.make_mesh` ->
`llama.make_train_step` under plain SGD -> `mpi.stop()` on seeded token batches
resident on the device; one step queued behind the one that runs; the rate
from the median interval between completions, `harness.median_step_s`;
weights, batches, the reference check, compilation and warm-up in set-up) for a
stack whose every layer runs two mixers side by side on one normed input, an
attention branch and a Mamba-2 state-space branch, with constants on the
embedding, the logits, both branches and the FFN: Falcon-H1.  It builds
`llama.Config` from the configuration file with the fields such a model
needs, so a program that lacks them fails at once (`TypeError`), before
anything touches the device's memory.

Taken from the runners that have them, through `harness.load_module`:
`instruction_scopes` (`step_tokens_looped.py`); `self_ms` and
`kernel_instructions` (`step_tokens_latent.py`); compiling, warm-up, the
window, the joins and the result (`token_loop.py`).  Written here: `_model`,
`SCOPES`, the SGD step and the checks.

`correct` compares, all before the window (`ctx.counters["reference_check"]`
holds all of it; the limits and why are in `reference/<config>.py`):

* `compare.check` on the configuration's `check_sample`: the loss, the logits
  and every leaf's gradient norm against the plain reference;
* `branch_rel_max`: what the attention branch, the state-space branch and the
  FFN of the first and of the last layer each add to the residual on that
  sample, the program's account (`llama.branch_contributions`, the code the
  step runs) against what the reference's blocks formed on their way, the
  relative L2 error of each against its own norm, the largest of the six.
  The branches carry multipliers of 0.0375, 0.088 and 0.011: one left out or a
  constant dropped hides inside the logits' limit and reads 1 or more here;
* `update_rel_max`: ONE STEP OF THE STEP THAT IS TIMED (the jitted function
  `llama.make_train_step` returned, the one the window's executable is
  compiled from, here at the check sample's shape) from the seeded weights on
  that sample: the weights it hands back against the reference's,
  `sgd_first_step` on the REFERENCE's gradient, as the relative L2 distance
  `|ours - theirs| / |theirs - seeded|` over the whole tree and over every
  leaf of `UPDATE_LEAF_MIN` numbers a layer or more (all its layers
  together) that the reference's step moves in `UPDATE_MOVED_MIN` numbers or
  more, the largest.  A state left unchanged reads 1, a step of the wrong
  sign 2, a leaf left out 1.  It is far from 0 on a sound program and why is
  in `reference/<config>.py`;
* `scan_rel_max` and `scan_f32_rel_max`: the program's chunked scan alone
  (`ops.ssd.ssd`) at the configuration's head shapes on the sample's length,
  forward and backward, on a seeded probe whose every product the chunked
  form rounds is exact (`scan_probe`), against the reference's
  token-by-token scan and its gradient, each output read as how far it lies
  from the reference's float32 number beyond what rounding that number to
  its type costs (`beyond_rounding`): only float32's own rounding enters, so
  a state, a decay sum or a step size that is not float32, on the way
  forward or back, reads a hundred times the program as it is where the
  norms above cannot tell it from the activations' own rounding.

What it leaves in `ctx.counters` beside what `step_tokens` leaves:
`ssd_chunks` (chunks of the scan a step runs forward: sequences x chunks a
sequence x layers); `branch_rel`, `update_rel` and `scan_rel` (the readings
by name; `update_rel` holds every large leaf's, read or not, and
`update_moved` the numbers the reference's step moved in each); with
`--trace 1`, joins of the one capture with the executable's text:
`scope_ms`, the innermost of `SCOPES` an instruction carries (`ssd` before
`ssm`, so `ssm` is the branch round its scan); `attn_scope_ms`, by the
outer name `attn` alone (`attn_ms`); `full_flash_kernel_ms`, the Mosaic flash
kernels under `attn`, by kernel; `ssm_parts_ms`, by `ssm.conv` and `ssm.norm`
alone, which is logged and no metric reads.
"""

import numpy as np

# Inner scopes first: an instruction under `ssm/ssd` is `ssd`'s.
SCOPES = ("ssd", "ssm", "optimizer", "head_loss", "final_norm", "attn", "ffn",
          "embed")


def _model(cfg):
    """`llama.Config` from the configuration file."""
    from torchmpi_tpu.models import llama

    for name, want in (("attention_bias", False), ("mamba_conv_bias", True),
                       ("mamba_proj_bias", False), ("mamba_rms_norm", True),
                       ("mamba_norm_before_gate", False),
                       ("mamba_use_mlp", True), ("mlp_bias", False),
                       ("projectors_bias", False), ("hidden_act", "silu"),
                       ("attn_layer_indices", None), ("rope_scaling", None),
                       ("tie_word_embeddings", False)):
        if cfg[name] != want:
            raise ValueError(f"{name} = {cfg[name]!r} is not implemented (the "
                             f"program has {want!r})")
    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads * mamba_d_head")
    n = cfg["num_hidden_layers"]
    return llama.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        embed_multiplier=cfg["embedding_multiplier"],
        head_multiplier=cfg["lm_head_multiplier"],
        attn_in_multiplier=cfg["attention_in_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        attn_out_multiplier=cfg["attention_out_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ffn_multipliers=tuple(cfg["mlp_multipliers"]),
        layer_kinds=(("attn+ssm", "dense"),) * n)


def _rel(off, of):
    import jax.numpy as jnp

    return jnp.linalg.norm(off) / jnp.maximum(jnp.linalg.norm(of), 1e-30)


def _values(a, dtype=None):
    """a in float32, its values those of `dtype` (a's own where none is
    given).  By `reduce_precision`: a pair of converts inside one program is
    the compiler's to drop, and with it the rounding that is compared."""
    import jax
    import jax.numpy as jnp

    to = jnp.finfo(dtype or a.dtype)
    return jax.lax.reduce_precision(a.astype(jnp.float32), to.nexp, to.nmant)


def branch_errors(ours, theirs):
    """{"<branch>.first" / ".last": the relative L2 error of what that branch
    of the first / last layer adds to the residual, against its own norm}."""
    return {f"{name}.{where}": _rel(ours[name][layer] - theirs[name][layer],
                                    theirs[name][layer])
            for name in ("attn", "ssm", "ffn")
            for where, layer in (("first", 0), ("last", -1))}


# A leaf this large a layer (every projection, the embedding, the head) is
# read by itself too; at the cell's rate a smaller one (a norm, a bias, a
# head's scalars) moves in a handful of its numbers or in none.
UPDATE_LEAF_MIN = 1 << 20
# ... where the reference's step moves this many of its numbers or more: a
# leaf whose step lies under half a unit in its weights' last place nearly
# everywhere (`wq` and `wk`, behind `key_multiplier`) moves in a dozen of its
# numbers, and the few that one side carries over a rounding edge and the
# other does not read 0 on one seed and 0.62 on the next with nothing wrong
# (the readings and the counts: `reference/<config>.py`).
UPDATE_MOVED_MIN = 256


def update_errors(seeded, ours, theirs, keep_axes):
    """-> ({"all" and the name of every leaf of `UPDATE_LEAF_MIN` numbers a
    layer or more: `|ours - theirs| / |theirs - seeded|`}, {the same names:
    how many numbers `theirs` moved}): the three trees are a step's weights
    before it, after it, and after the reference's step; float32 sums.
    `keep_axes` names the leaves that stack their layers."""
    import jax
    import jax.numpy as jnp

    import compare

    less = lambda a, b: jax.tree.map(lambda a, b: _values(a) - _values(b),
                                     a, b)
    off = compare.leaf_norms(less(ours, theirs), {})
    step = less(theirs, seeded)
    moved = compare.leaf_norms(step, {})
    counts = [jnp.sum(leaf != 0) for leaf in jax.tree.leaves(step)]
    whole = lambda norms: jnp.sqrt(sum(n * n for n in norms.values()))
    found = {"all": whole(off) / jnp.maximum(whole(moved), 1e-30)}
    numbers = {"all": sum(counts)}
    for name, leaf, n in zip(off, jax.tree.leaves(seeded), counts):
        layers = leaf.shape[0] if keep_axes.get(name) else 1
        if leaf.size // layers >= UPDATE_LEAF_MIN:
            found[name] = off[name] / jnp.maximum(moved[name], 1e-30)
            numbers[name] = n
    return found, numbers


def update_read(found, numbers):
    """The readings `update_rel_max` is the largest of: the whole tree's, and
    a leaf's where the reference's step moved `UPDATE_MOVED_MIN` numbers."""
    return {k: v for k, v in found.items()
            if k == "all" or numbers[k] >= UPDATE_MOVED_MIN}


SCAN_OUTPUTS = ("y", "dx", "ddt", "dA", "dB", "dC", "dD")
# What the two limits read: of the outputs that come rounded to the inputs'
# type, and of those that come in float32.  Not dB and dC: the chunked form
# rounds, by its design, `M`, `dt x` and the cotangent of `C B^T` to the
# inputs' type; on the probe the first two are exact and the third is not,
# and it reaches dB and dC alone.  The five read the same states, decay sums
# and step sizes, each way.
SCAN_ROUNDED, SCAN_FLOAT32 = ("y", "dx"), ("ddt", "dA", "dD")


def scan_probe(cfg, seed, seq_len, dtype):
    """The inputs of `scan_rel_max`, (x, dt, A, B, C, D, dy) with a batch of
    1, from `seed`: whole numbers, x in 0..15, B and C in {0, 1}, the
    cotangent dy in -3..3, D 1, A = -ln 2 a head, and dt 0 at most tokens
    (nothing is written and nothing decays; the state is read), 1 or 2 a head
    at one token in 16, so that every decay is a power of two and `C B^T`
    (at most 256), `M`, `dt x` and what a chunk writes are exact in bfloat16
    operands, while the state sums products of many sizes and needs float32.
    One pair of tokens in 32 (2i, 2i + 1) is two HALF steps, dt 257/256 and
    255/256 (the first is no bfloat16 number) on every head, with x 0 at both
    and C 0 at the first, so that nothing reads or writes between them and
    all that leaves the pair is a decay of 1/4: a dt that is not float32
    makes it 2^-(511/256)."""
    rng = np.random.default_rng(seed)
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    whole = lambda low, high, *shape: rng.integers(low, high,
                                                   (1, seq_len, *shape))
    x, dy = whole(0, 16, H, P), whole(-3, 4, H, P)
    B, C = whole(0, 2, G, N), whole(0, 2, G, N)
    dt = np.where(rng.random((1, seq_len, 1)) < 1 / 16, whole(1, 3, H),
                  0).astype(np.float32)
    first = 2 * np.flatnonzero(rng.random(seq_len // 2) < 1 / 32)
    dt[:, first], dt[:, first + 1] = 257 / 256, 255 / 256
    x[:, first] = x[:, first + 1] = C[:, first] = 0
    return (x.astype(dtype), dt, np.full((H,), -np.log(2), np.float32),
            B.astype(dtype), C.astype(dtype), np.ones((H,), np.float32),
            dy.astype(dtype))


def beyond_rounding(ours, theirs):
    """`max(|ours - theirs| - |rounded(theirs) - theirs|, 0)`, number by
    number: how much farther `ours` lies from `theirs` (float32) than theirs
    rounded to ours' type does; 0 where ours IS that rounding."""
    import jax.numpy as jnp

    return jnp.maximum(jnp.abs(_values(ours) - theirs)
                       - jnp.abs(_values(theirs, ours.dtype) - theirs), 0)


def scan_errors(scan, reference_scan, probe):
    """{each of `SCAN_OUTPUTS`: how far `scan`'s output, and each of its six
    gradients, on `probe` lies from `reference_scan`'s (one sequence, float32,
    at the highest precision) BEYOND what rounding the reference's own number
    to the type ours comes in costs: the L2 norm of `max(|ours - theirs| -
    |rounded(theirs) - theirs|, 0)` over that of theirs}.  For an output that
    comes in float32 that is the relative L2 error.  For one that comes
    rounded, a number that float32's noise carries over a rounding edge costs
    twice its distance from the edge, the noise's size, and not the unit in
    the last place that the plain difference of the two rounded numbers
    reads: a few such numbers among the largest made that difference 3e-5
    on one seed and 1.9e-4 on the next."""
    import jax
    import jax.numpy as jnp

    *inputs, dy = probe
    y, pull = jax.vjp(scan, *inputs)
    ours = (y, *pull(dy))
    one = lambda a: a[0].astype(jnp.float32)
    x, dt, A, B, C, D = inputs
    with jax.default_matmul_precision("highest"):
        y, pull = jax.vjp(reference_scan, one(x), dt[0], A, one(B), one(C), D)
        theirs = (y, *pull(one(dy)))
    # dx at a half step (x is 0 there) holds `M^T dy` of a row whose `M` is
    # no power of two, rounded by design: those rows are left out.
    whole = (dt[0] == jnp.round(dt[0]))[..., None]
    found = {}
    for name, a, b in zip(SCAN_OUTPUTS, ours, theirs):
        keep = whole if name == "dx" else 1
        found[name] = _rel(beyond_rounding(a.reshape(b.shape), b) * keep,
                           b * keep)
    return found


# ------------------------------------------------------------------ the run

def run(ctx):
    cfg, mix, how = ctx.cfg, ctx.traffic, ctx.cfg["run"]
    model = _model(cfg)         # a program without the fields stops here

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.ops import ssd
    from torchmpi_tpu.parallel import make_mesh

    import compare
    import harness
    import traffic as traffic_mod

    looped = harness.load_module("runners", "step_tokens_looped")
    latent = harness.load_module("runners", "step_tokens_latent")
    loop = harness.load_module("runners", "token_loop")
    devices = jax.devices()[:ctx.chips]
    dtype = jnp.dtype(how["dtype"])
    kinds = dict(attn=how["attn"], remat=how["remat"])
    check = cfg["check_sample"]
    chunk = min(how["loss_chunk"], check["seq_len"])
    if (check["batch"] != mix["batch"]
            or check["seq_len"] < 2 * cfg["mamba_chunk_size"]):
        raise ValueError("the check sample has the timed batch's rows and at "
                         "least two chunks of the scan, or no state crosses "
                         "a chunk's edge in it")

    mpi.start(devices=devices)
    mesh = make_mesh(mix["mesh"], devices=devices)
    seeded = jax.jit(lambda: llama.init(jax.random.PRNGKey(ctx.seed), model,
                                        dtype=dtype))
    with ctx.compiling("seeded weights"):
        params = llama.shard_params(seeded(), mesh, model)
        jax.block_until_ready(params)
    step = llama.make_train_step(model, mesh, lr=how["lr"],
                                 loss_chunk=how["loss_chunk"], **kinds)

    # (a) the system against the plain reference, on the check sample.
    reference = ctx.module("reference")
    sample = tuple(jnp.asarray(a) for a in traffic_mod.tokens(
        mix, cfg, ctx.seed + 1, n_batches=1, batch=check["batch"],
        seq_len=check["seq_len"])[0])
    grad_fn = jax.value_and_grad(
        llama.make_loss_fn(model, mesh, loss_chunk=chunk, **kinds))

    def system(p, s):
        loss, grads = grad_fn(p, s)
        return loss, llama.apply(model, p, s[0], mesh=mesh, **kinds), grads

    with ctx.compiling("reference check"):
        found = compare.check(
            system, lambda p, s: reference.loss_and_grads(cfg, p, s)[:3],
            params, sample, reference.TOLERANCE, reference.LEAF_AXES)

    # (b) each branch's own contribution, first and last layer, and (c) one
    # step of the step that is timed, against one pass of the reference.
    def against(p, stepped, ours, s):
        _, _, grads, theirs = reference.loss_and_grads(cfg, p, s)
        return (branch_errors(ours, theirs), update_errors(
            p, stepped, reference.sgd_first_step(p, grads, how["lr"]),
            reference.LEAF_AXES))

    with ctx.compiling("branches and one step"):
        ours = jax.jit(lambda p, t: llama.branch_contributions(
            model, p, t, mesh=mesh, attn=how["attn"]))(params, sample[0])
        stepped, _, _ = step(params, None, *sample)     # takes `params`
        params = llama.shard_params(seeded(), mesh, model)
        branch, (update, moved) = jax.device_get(jax.jit(against)(
            params, stepped, ours, sample))
        del ours, stepped
    ctx.counters["update_moved"] = {k: int(v) for k, v in moved.items()}
    for name, readings in (("branch_rel", branch), ("update_rel", update)):
        ctx.counters[name] = {k: float(v) for k, v in readings.items()}
        ctx.mark(f"{name}: {ctx.counters[name]}")
    ctx.mark(f"update_moved: {ctx.counters['update_moved']}")
    found["branch_rel_max"] = max(ctx.counters["branch_rel"].values())
    found["update_rel_max"] = max(update_read(
        ctx.counters["update_rel"], ctx.counters["update_moved"]).values())

    # (d) the scan alone, forward and backward, on the probe.
    with ctx.compiling("scan probe"):
        probe = tuple(jnp.asarray(a) for a in scan_probe(
            cfg, ctx.seed + 2, check["seq_len"], dtype))
        ctx.counters["scan_rel"] = {
            k: float(v) for k, v in jax.device_get(jax.jit(
                lambda probe: scan_errors(
                    lambda *a: ssd.ssd(*a, chunk=model.ssm_chunk),
                    reference.scan, probe))(probe)).items()}
        del probe
    for name, read in (("scan_rel_max", SCAN_ROUNDED),
                       ("scan_f32_rel_max", SCAN_FLOAT32)):
        found[name] = max(ctx.counters["scan_rel"][k] for k in read)
    ctx.mark(f"scan_rel: {ctx.counters['scan_rel']}")
    found["ok"] = bool(
        found["ok"] and all(np.isfinite(found[k]) and found[k] <= limit
                            for k, limit in reference.MORE_TOLERANCE.items()))
    ctx.counters["reference_check"] = found

    batch_sharding = NamedSharding(mesh, P("dp", None))
    batches = [tuple(jax.device_put(a, batch_sharding) for a in pair)
               for pair in traffic_mod.tokens(mix, cfg, ctx.seed)]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    ctx.counters["ssd_chunks"] = (
        mix["batch"] * ssd.n_chunks(mix["seq_len"], model.ssm_chunk)
        * model.n_layers)
    compiled, hlo = loop.compile_step(ctx, step, params, None, *batches[0])
    state = [params]
    del params

    def one_step(n):
        state[0], _, loss = compiled(state[0], None,
                                     *batches[n % len(batches)])
        return loss

    ctx.mark(f"warmed up, {loop.warm_up(one_step)} fenced steps; "
             f"{ctx.counters['ssd_chunks']} chunks of the scan a step")
    losses, step_s, window_s = loop.window(ctx, mix, one_step, tokens_per_step,
                                           state=lambda: state[0])
    if ctx.trace:
        loop.join(ctx, latent.self_ms, {
            "scope_ms": looped.instruction_scopes(hlo, SCOPES),
            "attn_scope_ms": looped.instruction_scopes(hlo, ("attn",)),
            "full_flash_kernel_ms": latent.kernel_instructions(hlo, "attn"),
            "ssm_parts_ms": looped.instruction_scopes(
                hlo, ("ssm.conv", "ssm.norm"))})
    state.clear()
    mpi.stop()
    return loop.result(ctx, losses, tokens_per_step, step_s, window_s, devices)
