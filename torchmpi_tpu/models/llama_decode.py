"""Autoregressive decoding for ``models/llama``: the K/V cache, one decode
step with a position for each sequence, batched prefill, and the compiled
generation loop round them.  ``serving/engine.py:LlamaRunner`` drives the same
prefill and step, a sequence a slot.

What a configuration must be for these to run it is ``llama._LACKS``'s to
say: each of the three opens with its one :func:`llama._refuse`.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_TP
from ._common import mesh_spec as _mesh_spec
from .llama import (Config, Params, _NEG_INF, _decoder_layer,
                    _make_attn_impl, _moe_ffn, _qk_norm, _refuse, rms_norm,
                    rope)


def init_kv_cache(cfg: Config, batch: int, max_len: int,
                  dtype=jnp.float32) -> Params:
    """Per-layer K/V cache at native GQA head count, stacked on the layer
    axis to match the stacked parameters (one ``lax.scan`` drives both)."""
    hd, KV = cfg.head_dim, cfg.n_kv_heads
    shape = (cfg.n_layers, batch, max_len, KV, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _decode_step(cfg: Config, params: Params, cache: Params,
                 tokens: jax.Array, pos: jax.Array):
    """One autoregressive position for every sequence of the batch, each at
    its own: tokens (B,) int32 at positions ``pos`` (B,) -> (logits (B, V)
    f32, updated cache).  Row b is rotated by ``pos[b]``'s angles, its key and
    value go to ``pos[b]`` of its stripe of the cache, and its attention reads
    that stripe up to and including ``pos[b]`` (whatever the later slots hold
    is masked off).  :func:`make_generate_fn` keeps its batch in step and
    passes one position broadcast; a serving batch has a position a slot.

    The write is a ``dynamic_update_slice`` a row, B rows of KV x hd into the
    carried cache: a select against ``arange(max_len) == pos[b]`` would pass
    over the whole cache once more a layer."""
    _refuse(cfg, "the decode step")
    B = tokens.shape[0]
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    scale = 1.0 / np.sqrt(hd)
    max_len = cache["k"].shape[2]
    seen = jnp.arange(max_len)[None, None, None, :] <= pos[:, None, None, None]
    write = jax.vmap(lambda c, new, p: lax.dynamic_update_slice(
        c, new.astype(c.dtype), (p, 0, 0)))
    h = params["embed"][tokens]                      # (B, D)

    def layer(h, xs):
        lp, ck, cv = xs                              # ck/cv: (B, max_len, KV, hd)
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k_new = _qk_norm(cfg, lp, x @ lp["wq"], x @ lp["wk"])
        # :func:`rope` turns row l of its second axis by ``positions[l]``: the
        # batch rides there, a sequence of B rows each at its own position.
        q = rope(q.reshape(1, B, H, hd), pos, cfg.rope_theta)[0]
        k_new = rope(k_new.reshape(1, B, KV, hd), pos, cfg.rope_theta)[0]
        ck = write(ck, k_new[:, None], pos)
        cv = write(cv, (x @ lp["wv"]).reshape(B, 1, KV, hd), pos)
        # GQA attention of the single query against the cache, f32 softmax.
        # Grouped contraction against the cache at its native KV head count
        # — repeating the cache to H heads would multiply the dominant HBM
        # read of the decode step by H/KV.
        rep = H // KV
        qg = q.reshape(B, KV, rep, hd).astype(jnp.float32)
        s = jnp.einsum("bgrd,blgd->bgrl", qg,
                       ck.astype(jnp.float32)) * scale
        s = jnp.where(seen, s, _NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrl,blgd->bgrd", w, cv.astype(jnp.float32))
        h = h + (o.reshape(B, H * hd).astype(h.dtype) @ lp["wo"])
        x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        if cfg.n_experts:
            # Dropless at decode: capacity = tokens-per-group covers the
            # worst case (top-k experts are distinct, so an expert gets at
            # most one unit per token), so routing never depends on bucket
            # pressure.
            g, _ = _moe_ffn(cfg, lp, x[:, None, :], dropless=True)
            return h + g[:, 0], (ck, cv)
        g = jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])
        return h + g @ lp["w_down"], (ck, cv)

    h, (new_k, new_v) = lax.scan(layer, h,
                                 (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    logits = (h @ params["head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def _prefill(cfg: Config, params: Params, cache: Params,
             prompt: jax.Array, attn: str = "auto",
             mesh: Optional[Mesh] = None):
    """Batched prefill: ONE full forward over the prompt (matmul-bound, the
    parameters stream from HBM once) seeding the K/V cache, instead of
    prompt_len matrix-vector decode steps.  Returns (last-position logits,
    cache).

    ``attn="auto"`` picks the prefill attention by prompt length: full for
    short prompts (XLA's fused attention is fine and tiles freely), the
    Pallas flash kernels once the prompt's (Lp, Lp) score matrix is the
    memory term that matters (>= 1024, where flash also wins on time —
    the Llama table in BASELINE.md) and a legal tile divides ``Lp``.
    ``mesh`` is the mesh the params are sharded on, if any: the flash
    kernel needs it to run per batch/head shard.
    """
    _refuse(cfg, "prefill")
    B, Lp = prompt.shape
    positions = jnp.arange(Lp)
    scale = 1.0 / np.sqrt(cfg.head_dim)
    if attn == "auto":
        attn = "full"
        if Lp >= 1024:
            # Tile legality is _auto_block's call, not a duplicated
            # divisibility literal here — illegal lengths stay on the
            # full path instead of erroring.
            from ..ops.flash_attention import _auto_block

            try:
                _auto_block(Lp)
                attn = "flash"
            except ValueError:
                pass
    attn_impl = _make_attn_impl(cfg, attn, mesh, scale)
    h = params["embed"][prompt]

    def layer(h, xs):
        lp, ck, cv = xs
        h, _, (k, v) = _decoder_layer(cfg, lp, h, positions, attn_impl,
                                      with_kv=True, mesh=mesh)
        ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, 0, 0))
        return h, (ck, cv)

    h, (new_k, new_v) = lax.scan(layer, h,
                                 (params["layers"], cache["k"], cache["v"]))
    h = rms_norm(h[:, -1], params["norm"], cfg.norm_eps)
    logits = (h @ params["head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def make_generate_fn(cfg: Config, prompt_len: int, max_new: int,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 0.0, mesh: Optional[Mesh] = None):
    """Compiled autoregressive generation:
    ``fn(params, prompt (B, prompt_len) int32, rng) -> (B, max_new) int32``.

    One compiled program: a batched prefill forward seeds the K/V cache,
    then a ``lax.scan`` of single-position decode steps (cache in the
    carry — static shapes, no host round-trips).  ``temperature=0`` is
    greedy; otherwise tokens are sampled from softmax(logits / temperature),
    optionally filtered first by ``top_k`` (keep the k highest logits) and
    ``top_p`` (nucleus: keep the smallest prefix of the sorted distribution
    whose probability mass reaches p; the top token always survives).
    Both filters are static-shape mask-and-renormalize forms — no
    data-dependent shapes, so the whole sampler stays inside the compiled
    scan.

    **Distributed generation** (``mesh``): pass params placed by
    :func:`shard_params` and the mesh they live on.  Weights stay in their
    Megatron layout (never gathered), the batch shards over ``dp``, and
    the K/V cache — the array that grows with context and would otherwise
    replicate — is PINNED sharded over dp x tp (tp on the KV-head axis,
    matching the column-sharded wk/wv that produce it), through prefill
    and every decode tick.  This is what makes the flagship samplable at
    all: full-8B bf16 params are 16.1 GB against a 16 GB chip
    (BASELINE.md projection), so decode must run tp-sharded with
    per-shard caches.  Token-exact vs the single-device oracle (greedy;
    tested at tiny geometry on the virtual mesh).  Sampling collectives
    (the per-layer attention/MLP psums) are GSPMD's, inferred from the
    pinned weight + cache shardings.
    """
    _refuse(cfg, "make_generate_fn")
    if prompt_len < 1 or max_new < 1:
        raise ValueError("prompt_len and max_new must be >= 1")
    if mesh is not None and cfg.n_kv_heads % dict(mesh.shape).get(AXIS_TP, 1):
        raise ValueError(
            f"tp={dict(mesh.shape).get(AXIS_TP)} must divide n_kv_heads "
            f"{cfg.n_kv_heads} (the cache shards on the KV-head axis)")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if top_k < 0 or (top_k and top_k > cfg.vocab):
        raise ValueError(f"top_k must be in [0, {cfg.vocab}], got {top_k}")
    if temperature <= 0.0 and (top_k or top_p):
        # Greedy ignores the filters; silently doing so would let a caller
        # believe they sampled.
        raise ValueError("top_k/top_p require temperature > 0 "
                         "(temperature=0 is greedy)")
    max_len = prompt_len + max_new

    def constrain_cache(cache):
        if mesh is None:
            return cache
        # (n_layers, B, max_len, KV, hd): batch over dp, KV heads over tp.
        spec = _mesh_spec(P(None, AXIS_DP, None, AXIS_TP, None), mesh)
        sh = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda a: lax.with_sharding_constraint(a, sh), cache)

    def constrain_logits(x):
        if mesh is None:
            return x
        # (B, V) — batch over dp, vocab gathered for the sampler (2 MB at
        # 8B width; sort/cumsum over a sharded vocab axis buys nothing).
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, _mesh_spec(P(AXIS_DP, None), mesh)))

    def fn(params: Params, prompt: jax.Array, rng: jax.Array) -> jax.Array:
        if prompt.shape[1] != prompt_len:
            raise ValueError(f"prompt has length {prompt.shape[1]}, "
                             f"generate_fn was built for {prompt_len}")
        B = prompt.shape[0]
        cache0 = constrain_cache(
            init_kv_cache(cfg, B, max_len, params["embed"].dtype))
        logits, cache = _prefill(cfg, params, cache0, prompt, mesh=mesh)
        cache = constrain_cache(cache)
        logits = constrain_logits(logits)

        def pick(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            l = (logits / temperature).astype(jnp.float32)
            neg = jnp.asarray(-1e30, l.dtype)
            if top_k:
                # Keep the k highest logits (kth value as threshold).
                kth = lax.top_k(l, top_k)[0][..., -1:]
                l = jnp.where(l < kth, neg, l)
            if 0.0 < top_p < 1.0:
                # Nucleus: drop tokens whose EXCLUSIVE cumulative mass (in
                # descending-probability order) already reached p; the top
                # token's exclusive mass is 0, so it always survives.
                sorted_l = jnp.sort(l, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(sorted_l, axis=-1)
                cum_excl = jnp.cumsum(probs, axis=-1) - probs
                cut = jnp.sum((cum_excl < top_p).astype(jnp.int32), axis=-1)
                # Threshold = smallest kept (sorted) logit.
                thresh = jnp.take_along_axis(
                    sorted_l, jnp.maximum(cut[..., None] - 1, 0), axis=-1)
                l = jnp.where(l < thresh, neg, l)
            return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)

        def decode(carry, i):
            cache, logits, key = carry
            key, sub = jax.random.split(key)
            tok = pick(logits, sub)
            logits, cache = _decode_step(cfg, params, cache, tok,
                                         jnp.full((B,), prompt_len + i))
            # Re-pin the carried cache/logits every tick: without the
            # constraint GSPMD is free to settle the scan carry on a
            # replicated layout (the cache is the array that cannot
            # replicate at 8B).
            return (constrain_cache(cache), constrain_logits(logits),
                    key), tok

        # max_new - 1 cache-advancing steps; the last token needs only a
        # pick from the final logits (no wasted trailing forward).
        (_, logits, key), toks = lax.scan(decode, (cache, logits, rng),
                                          jnp.arange(max_new - 1))
        _, sub = jax.random.split(key)
        last = pick(logits, sub)
        return jnp.concatenate([toks, last[None]], axis=0).T  # (B, max_new)

    return jax.jit(fn)

