"""The expert layer on an ``ep`` axis no wider than the choices a token
(``llama._ep_form``: ``ep <= expert_top_k``): it gathers the TOKENS over the
axis, runs the held experts on every rank's rows in one order by expert and
sends the partial sums home (``llama._ep_gathered``), where a wider axis
exchanges the units (``llama._ep_experts``, ``tests/test_mellum2_passes.py``).
One layer at toy widths on the suite's host devices, ``ep`` = 4 with 16
experts and 4 a token: the gathered form against the unit exchange FORCED on
the same inputs, the units counted where they ran, a rank that draws twice the
share, a planted fault, the rule, the account (``llama.ep_exchange_plan``)
against the collectives of the layer's jaxpr, the passes a rank took
(``llama.ep_pass_counts``), and a body traced once a shape."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama

from test_mellum2 import (ep_mesh, file_of, layer_of, mellum_tiny, rel,
                          reference)  # noqa: F401 — reference is a fixture
from test_mellum2_passes import traced_anew  # noqa: F401 — a fixture

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

EP, B, L = 4, 8, 32
T = B * L // EP             # a rank's tokens


def _layer(k=4, dtype=jnp.float32, skewed=()):
    """One expert layer of 16 experts, ``k`` a token: its configuration, its
    weights on ``ep`` = 4 and a batch of 8 x 32 tokens.  ``skewed``: experts
    that every token chooses (their router columns read a constant channel
    of the input)."""
    cfg = mellum_tiny(n_layers=1, n_experts=16, k=k)
    lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg, dtype=dtype), 0)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, L, cfg.d_model), dtype)
    if skewed:
        x = x.at[..., 0].set(5.0)
        lp = {**lp, "router": lp["router"].at[0, jnp.asarray(skewed)].set(4)}
    spec = jax.tree.map(lambda s: jax.sharding.PartitionSpec(*s[1:]),
                        llama.param_specs(cfg)["layers"][0],
                        is_leaf=lambda s: isinstance(
                            s, jax.sharding.PartitionSpec))
    return cfg, llama.shard_by_specs(lp, ep_mesh(), spec), x


def _run(cfg, lp, x, grads=True):
    """``(y, delivered, grads)`` of the layer on ``ep`` = 4: the gradients of
    a probe of the result and of the auxiliary terms, by the weights and the
    input (None where none are asked for)."""
    mesh = ep_mesh()
    probe = jax.random.normal(jax.random.PRNGKey(7), x.shape, jnp.float32)

    def value(lp, x):
        y, (aux, delivered) = llama._moe_ffn(cfg, lp, x, mesh=mesh)
        return (jnp.sum(y.astype(jnp.float32) * probe) + jnp.sum(aux),
                (y, delivered))

    if not grads:
        y, delivered = jax.jit(value)(lp, x)[1]
        return y, np.asarray(delivered), None
    (_, (y, delivered)), grads = jax.jit(jax.value_and_grad(
        value, argnums=(0, 1), has_aux=True))(lp, x)
    return y, np.asarray(delivered), grads


def _as_units(monkeypatch):
    monkeypatch.setattr(llama, "_ep_form", lambda cfg, ep: "units")


def _assert_same(got, want, limit):
    (y, delivered, grads), (want_y, want_delivered, want_grads) = got, want
    assert rel(y, want_y) < limit
    np.testing.assert_array_equal(delivered, want_delivered)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert a.dtype == b.dtype
        assert rel(a, b) < limit, jax.tree_util.keystr(path)


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_gathered_form_is_the_unit_exchange(monkeypatch, dtype, limit):
    """Same inputs, both forms: the layer's result, the gradient of every
    leaf and of the input, and ``delivered`` entry by entry, which sums to
    ``k * B * L``: every unit ran, on the rank of its expert, and is counted
    by the rank its token came from."""
    cfg, lp, x = _layer(dtype=dtype)
    assert llama._ep_form(cfg, EP) == "tokens"
    got = _run(cfg, lp, x)
    _as_units(monkeypatch)
    _assert_same(got, _run(cfg, lp, x), limit)
    assert got[1].shape == (EP, EP) and got[1].sum() == 4 * B * L
    assert (got[1] > 0).all()


def test_a_fuller_rank_takes_more_passes_and_nothing_is_dropped(monkeypatch):
    """A router that sends two of every token's four choices to rank 2's
    experts: that rank draws more than twice the uniform share and takes
    more passes than the others, which no collective inside the loop stops
    it from; every unit still runs and the result is the unit exchange's."""
    cfg, lp, x = _layer(skewed=(8, 9))
    got = _run(cfg, lp, x)
    delivered = got[1]
    assert delivered.sum() == 4 * B * L
    by_rank = delivered.sum(axis=1)
    assert by_rank[2] > 2 * B * L           # the uniform share is B * L
    assert by_rank[2] > 1.5 * max(np.delete(by_rank, 2))
    rows = llama.ep_token_pass_rows(cfg, T, EP)
    assert rows == 4 * EP * T // 16                 # the mean expert's rows
    passes = -(-by_rank // rows)
    assert passes[2] > max(np.delete(passes, 2)) >= 1
    _as_units(monkeypatch)
    _assert_same(got, _run(cfg, lp, x), 1e-5)


def test_a_tokens_two_units_in_one_row_tile_are_both_added(reference):
    """One token of each row of the batch chooses experts 8 and 9 and no other
    token does: on rank 2, which holds both, the first pass's rows 0 to 7 are
    expert 8's and rows 8 to 15 expert 9's, the SAME eight tokens, in one row
    tile of the scatter-add (``ops/scatter_add_rows.py``, 64 rows here).  The
    kernel takes a tile one expert's segment at a time, so both units land on
    their token's sum, forward and in the rows' cotangents: the layer and its
    gradients are the plain reference's, which loops over the experts."""
    from torchmpi_tpu.ops.scatter_add_rows import row_tile

    cfg, lp, x = _layer()
    both = jnp.asarray((8, 9))
    x = x.at[..., 0].set(-5.0).at[:, 3, 0].set(5.0)
    lp = {**lp, "router": lp["router"].at[:, both].set(0).at[0, both].set(4)}
    xt = x.reshape(-1, cfg.d_model)
    units = np.asarray(reference.routed_units(file_of(cfg), lp, xt))
    assert units[8] == units[9] == B and units.sum() == 4 * B * L
    assert min(row_tile(cfg.d_model),
               llama.ep_token_pass_rows(cfg, T, EP)) >= 2 * B
    mesh = ep_mesh()
    probe = jax.random.normal(jax.random.PRNGKey(7), xt.shape, jnp.float32)
    ours = lambda lp, x: jnp.sum(probe * llama._moe_ffn(
        cfg, lp, x, mesh=mesh)[0].reshape(xt.shape))
    plain = lambda lp, x: jnp.sum(probe * reference.experts_ffn(
        file_of(cfg), lp, x.reshape(xt.shape)))
    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(lp, x)
                 for f in (ours, plain))
    assert rel(got[0], want[0]) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        assert rel(a, b) < 1e-5, jax.tree_util.keystr(path)


def _a_row_left_out(k, R, xt, wflat, order, arrived, p,
                    whole=llama._held_pass):
    token, unit, rows, xs, ws, kept = whole(k, R, xt, wflat, order, arrived,
                                            p)
    out = (jnp.arange(R) == 0) & (p == 0)
    return (jnp.where(out, xt.shape[0], token),
            jnp.where(out, xt.shape[0] * k, unit), rows & ~out[:, None], xs,
            ws, kept)


def test_a_row_left_out_of_a_pass_is_counted(traced_anew, monkeypatch):
    """``delivered`` is counted in the passes: a mask that leaves the first
    row out of every rank's first pass makes it fall short of the routers'
    ``k * B * L`` by a unit a rank (the twin of
    ``test_mellum2_passes.py::test_a_dropped_unit_is_counted``)."""
    cfg, lp, x = _layer()
    monkeypatch.setattr(llama, "_held_pass", _a_row_left_out)
    short = _run(cfg, lp, x, grads=False)[1]
    monkeypatch.undo()
    jax.clear_caches()
    whole = _run(cfg, lp, x, grads=False)[1]
    assert whole.sum() == 4 * B * L
    np.testing.assert_array_equal(short.sum(axis=1) + 1, whole.sum(axis=1))


def _inner(eqn):
    """The jaxprs an equation holds: a jitted call's, a loop's bodies."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _moved(jaxpr, width):
    """``(primitive, rows a rank sends)`` of every collective of ``jaxpr``,
    sub-jaxprs and loop bodies too, that moves rows ``width`` wide: a gather
    of (n, width) sends its n rows to the ep - 1 other ranks, an exchange of
    (ep, n, width) one block of n to each."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("all_gather", "all_to_all"):
            shape = eqn.invars[0].aval.shape
            if len(shape) > 1 and shape[-1] == width:
                found.append((eqn.primitive.name, (EP - 1) * shape[-2]))
        for sub in _inner(eqn):
            found += _moved(sub, width)
    return found


def _loop_bodies(jaxpr):
    for eqn in jaxpr.eqns:
        for sub in _inner(eqn):
            if eqn.primitive.name == "while":
                yield sub
            yield from _loop_bodies(sub)


def _counting(monkeypatch, *names):
    """The calls of ``llama``'s functions ``names`` from here on, by name."""
    calls = dict.fromkeys(names, 0)

    def counted(name, whole):
        def call(*args, **kwargs):
            calls[name] += 1
            return whole(*args, **kwargs)
        return call

    for name in names:
        monkeypatch.setattr(llama, name, counted(name, getattr(llama, name)))
    return calls


def _traced(cfg, lp, x):
    """The layer's forward pass and its forward and backward pass together,
    traced on ``ep`` = 4."""
    mesh = ep_mesh()
    layer = lambda lp, x: llama._moe_ffn(cfg, lp, x, mesh=mesh)[0]
    forward = jax.make_jaxpr(layer)(lp, x).jaxpr
    both = jax.make_jaxpr(
        lambda lp, x, dy: jax.vjp(layer, lp, x)[1](dy))(lp, x, x).jaxpr
    return forward, both


@pytest.mark.parametrize("k,form", [(4, "tokens"), (2, "units")])
def test_the_rule_and_the_plan_against_the_jaxpr(monkeypatch, k, form):
    """The form is read from the mesh and the configuration alone: ``ep`` = 4
    with 4 choices a token traces the gathered form, no ``_ep_experts`` and
    no exchange of a block of units; with 2 it traces ``_ep_experts`` and
    the all-to-alls of units, the gathered form not at all.  And
    ``ep_exchange_plan`` counts the rows that leave a rank as the layer's
    jaxpr has them: forward a gather and an exchange of a rank's tokens (two
    exchanges of a first pass's blocks, and two of an overflow pass's, in
    the loops' bodies), backward three."""
    called = _counting(monkeypatch, "_ep_experts", "_ep_gathered")
    cfg, lp, x = _layer(k=k)
    plan = llama.ep_exchange_plan(cfg, T, EP, itemsize=4)
    assert plan["form"] == form == llama._ep_form(cfg, EP)
    forward, both = _traced(cfg, lp, x)
    assert called == {"_ep_experts": 2 * (form == "units"),
                      "_ep_gathered": 2 * (form == "tokens")}
    fwd, all_of = _moved(forward, cfg.d_model), _moved(both, cfg.d_model)
    rows = lambda found, size=None: sum(
        n for _, n in found if size is None or n == (EP - 1) * size)
    bwd = lambda size=None: rows(all_of, size) - rows(fwd, size)
    assert plan["bytes_forward"] == plan["rows_forward"] * 4 * cfg.d_model
    assert plan["bytes_backward"] == plan["rows_backward"] * 4 * cfg.d_model
    if form == "tokens":
        assert sorted(fwd) == [("all_gather", 3 * T), ("all_to_all", 3 * T)]
        assert (rows(fwd), bwd()) == (plan["rows_forward"],
                                      plan["rows_backward"]) == (6 * T, 9 * T)
        assert (plan["rows_forward_overflow"],
                plan["rows_backward_overflow"]) == (0, 0)
        assert plan["pass_rows"] == plan["block_rows"] == (
            plan["overflow_pass_rows"]) == llama.ep_token_pass_rows(
                cfg, T, EP) == k * EP * T // 16
        return
    share, overflow = (llama.ep_pass_rows(cfg, T, EP),
                       llama.ep_overflow_rows(cfg, T, EP))
    assert {name for name, _ in all_of} == {"all_to_all"}
    assert (rows(fwd, share), bwd(share)) == (
        plan["rows_forward"], plan["rows_backward"]) == (6 * share, 9 * share)
    assert (rows(fwd, overflow), bwd(overflow)) == (
        plan["rows_forward_overflow"], plan["rows_backward_overflow"]) == (
            6 * overflow, 9 * overflow)
    assert rows(all_of) == 15 * (share + overflow)  # and no other block
    assert (plan["pass_rows"], plan["overflow_pass_rows"],
            plan["block_rows"]) == (EP * share, EP * overflow, overflow)


def test_the_plan_at_the_published_shapes():
    """Mellum2's layer on four chips, 2 x 8,192 tokens a chip: a pass of
    8,192 rows, 226 MB leave a chip a collective, five a layer (1.13 GB
    where the unit exchange's first passes alone were 2.26 GB); on 16 chips,
    wider than the 8 choices, the unit exchange."""
    cfg = llama.mellum2_12b_a2_5b()
    plan = llama.ep_exchange_plan(cfg, 2 * 8192, 4)
    assert (plan["form"], plan["pass_rows"], plan["block_rows"]) == (
        "tokens", 8192, 8192)
    assert plan["bytes_forward"] + plan["bytes_backward"] == 5 * 226_492_416
    assert llama.ep_exchange_plan(cfg, 2 * 8192, 8)["form"] == "tokens"
    wide = llama.ep_exchange_plan(cfg, 2 * 8192, 16)
    assert (wide["form"], wide["pass_rows"], wide["block_rows"]) == (
        "units", 16 * 8192, 2048)
    units = dataclasses.replace(cfg, expert_top_k=2)
    assert llama.ep_exchange_plan(units, 2 * 8192, 4)["form"] == "units"


@pytest.mark.parametrize("k,skewed", [(4, False), (4, True), (2, False)],
                         ids=["tokens", "tokens-skewed", "units"])
def test_the_passes_a_rank_took_are_read_beside_the_step(k, skewed):
    """``llama.ep_pass_counts`` on a stack of two layers: (layers, ranks),
    from what the passes counted.  The gathered form's ranks take the passes
    their own arrivals fill: near the uniform share the mean expert's rows a
    held expert and one for the remainder, and with every token choosing two
    of rank 2's experts that rank those units' eight passes and more; the
    unit exchange's ranks all take the fullest pair's."""
    cfg = mellum_tiny(n_layers=2, n_experts=16, k=k)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    if skewed:      # as _layer's: a constant channel that two columns read
        params = {**params, "embed": params["embed"].at[:, 0].set(5.0),
                  "layers": tuple({**run, "router": run["router"].at[
                      :, 0, jnp.asarray((8, 9))].set(4)}
                      for run in params["layers"])}
    mesh = ep_mesh()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab)
    passes = np.asarray(jax.jit(lambda p, t: llama.ep_pass_counts(
        cfg, p, t, mesh, attn="flash"))(
            llama.shard_params(params, mesh, cfg), tokens))
    assert passes.shape == (2, EP) and passes.dtype == np.int32
    if k == 2:
        assert (passes == passes[:, :1]).all() and (passes >= 1).all()
    elif skewed:
        assert (passes[:, 2] >= 8).all()    # two of four choices: 8 passes
        assert passes[1, 2] > np.delete(passes[1], 2).max()
    else:
        assert (passes >= 2).all() and (passes <= 7).all()
        assert 4 <= passes.mean() <= 5      # 4 held experts' mean rows, + 1
    with pytest.raises(ValueError, match="ep axis alone shares the tokens"):
        llama.ep_pass_counts(cfg, params, tokens, ep_mesh({"dp": 2, "ep": 4}))


def test_a_body_is_traced_once_a_shape(traced_anew, monkeypatch):
    """Building the ``ep`` = 4 train step of a stack that gathers tokens,
    under ``remat="full"``: ONE call of ``_held_swiglu`` (the forward loop's
    body) and ONE of ``_held_swiglu_bwd`` (the backward loop's), five
    ``_grouped_matmul``s, whatever the number of layers, replays and passes
    (the unit exchange's two pass sizes make it two, two and ten:
    ``tests/test_mellum2_passes.py``); and no exchange inside a loop."""
    import optax

    cfg = mellum_tiny(n_layers=2, n_experts=16, k=4)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    calls = _counting(monkeypatch, "_held_swiglu", "_held_swiglu_bwd",
                      "_grouped_matmul")
    mesh = ep_mesh()
    optimizer = optax.adamw(1e-2)
    p = llama.shard_params(params, mesh, cfg)
    tokens = jnp.zeros((B, L), jnp.int32)
    step = llama.make_train_step(
        cfg, mesh, attn="flash", optimizer=optimizer, remat="full",
        loss_chunk=32, with_delivered=True)
    jaxpr = jax.make_jaxpr(step)(p, optimizer.init(p), tokens, tokens).jaxpr
    assert calls == {"_held_swiglu": 1, "_held_swiglu_bwd": 1,
                     "_grouped_matmul": 5}

    bodies = list(_loop_bodies(jaxpr))
    assert bodies and not any(_moved(body, cfg.d_model) for body in bodies)
