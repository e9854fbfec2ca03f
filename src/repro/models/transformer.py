"""Decoder-only transformer stack covering the dense / MoE / VLM / hybrid
families, with layer-scan + remat, KV caches, and the three entry points the
launcher lowers: ``train_forward``, ``prefill``, ``decode_step``.

Family wiring:
* dense  — llama3.2-1b, qwen3-14b (qk-norm), gemma-7b (GeGLU, head 256),
           gemma2-27b (local/global alternating windows, softcaps, post-norms)
* moe    — qwen2-moe (shared experts + 60→64-padded routed top-4),
           arctic (dense FFN residual ∥ 128-expert top-2 MoE)
* vlm    — qwen2-vl backbone (M-RoPE; patch embeddings arrive pre-computed —
           the modality frontend is a stub per the assignment)
* hybrid — zamba2 (Mamba2 trunk in 6-layer scan segments, a *shared-weight*
           attention block every ``shared_attn_every`` layers, each occurrence
           with its own KV cache)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.context import shard_hint
from .layers import (
    Params,
    attention_params,
    dense_init,
    embed_init,
    latent_attention,
    mlp,
    mlp_params,
    multihead_attention,
    rmsnorm,
    rmsnorm_init,
    softcap,
)
from .moe import moe_apply, moe_params
from .ssm import mamba2_block, mamba2_init_state, mamba2_params


# --------------------------------------------------------------------------
# per-layer params
# --------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def layer_params(key, cfg: ModelConfig, layer: int = 0) -> Params:
    """Block ``layer``'s params: attention (MLA when ``cfg.is_mla``), then a
    MoE (``cfg.is_moe_layer(layer)``) or a dense MLP of ``d_ff``."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {
        "ln_attn": rmsnorm_init(cfg.d_model),
        "attn": attention_params(ks[0], cfg, dt),
        "ln_mlp": rmsnorm_init(cfg.d_model),
    }
    if cfg.post_block_norm:
        p["ln_attn_post"] = rmsnorm_init(cfg.d_model)
        p["ln_mlp_post"] = rmsnorm_init(cfg.d_model)
    if cfg.is_moe_layer(layer):
        p["moe"] = moe_params(ks[1], cfg, dt)
        if cfg.dense_parallel_ff and cfg.d_ff:
            p["mlp"] = mlp_params(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dt)
        if cfg.n_shared_experts and cfg.shared_d_ff:
            p["shared_mlp"] = mlp_params(ks[3], cfg.d_model, cfg.shared_d_ff, cfg.activation, dt)
    else:
        p["mlp"] = mlp_params(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dt)
    return p


def layer_windows(cfg: ModelConfig) -> List[int]:
    """Per-layer attention window, one Python int per layer.

    gemma2 alternates Local/Global; global layers get a huge window (≡ full
    attention).  The ints stay static for the unrolled stack and the stage
    executor (the Pallas kernel specializes on them); the layer scan turns
    them into an int32 ``xs`` array."""
    big = 1 << 30
    if cfg.sliding_window is None:
        return [big] * cfg.n_layers
    pattern = cfg.local_global_pattern or "LG"
    return [
        cfg.sliding_window if pattern[i % len(pattern)] == "L" else big
        for i in range(cfg.n_layers)
    ]


def block_apply(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    window,                                    # int, or int32 scalar traced
                                               # through the layer scan
    kv_cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,        # [B] fused-batch valid rows
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]], jax.Array]:
    """One transformer block; returns (x, new_cache, aux_loss)."""
    eps = cfg.norm_eps
    h = rmsnorm(x, p["ln_attn"], eps)
    attention = latent_attention if cfg.is_mla else multihead_attention
    attn_out, new_cache = attention(
        p["attn"], h, cfg,
        positions=positions,
        kv_cache=kv_cache,
        cache_pos=cache_pos,
        layer_window=window,
        q_lens=q_lens,
    )
    if cfg.post_block_norm:
        attn_out = rmsnorm(attn_out, p["ln_attn_post"], eps)
    x = x + attn_out

    h = rmsnorm(x, p["ln_mlp"], eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        moe_out, aux = moe_apply(p["moe"], h, cfg)
        if "mlp" in p:                       # arctic: dense FFN in parallel
            moe_out = moe_out + mlp(p["mlp"], h, cfg.activation)
        if "shared_mlp" in p:                # qwen2-moe shared experts
            moe_out = moe_out + mlp(p["shared_mlp"], h, cfg.activation)
        ff_out = moe_out
    else:
        ff_out = mlp(p["mlp"], h, cfg.activation)
    if cfg.post_block_norm:
        ff_out = rmsnorm(ff_out, p["ln_mlp_post"], eps)
    x = x + ff_out
    return x, new_cache, aux


# --------------------------------------------------------------------------
# model params
# --------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 8)
    p: Params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)}
    p["ln_final"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dt)

    if cfg.family == "hybrid":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        mk = lambda k: {"ln": rmsnorm_init(cfg.d_model), "mamba": mamba2_params(k, cfg, dt)}
        p["mamba_layers"] = jax.vmap(mk)(lkeys)
        # one SHARED attention block (weights reused at every occurrence)
        p["shared_proj_in"] = dense_init(keys[3], 2 * cfg.d_model, cfg.d_model, dt)
        p["shared_block"] = layer_params(keys[4], cfg)
    elif cfg.family == "ssm":
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        mk = lambda k: {"ln": rmsnorm_init(cfg.d_model), "mamba": mamba2_params(k, cfg, dt)}
        if cfg.scan_layers:
            p["layers"] = jax.vmap(mk)(lkeys)
        else:
            p["layers"] = [mk(k) for k in lkeys]
    else:
        lkeys = jax.random.split(keys[2], cfg.n_layers)
        if cfg.scan_layers:
            if cfg.first_k_dense:
                raise ValueError(
                    f"{cfg.name}: layers of two kinds cannot be stacked for "
                    "the layer scan; use scan_layers=False"
                )
            p["layers"] = jax.vmap(lambda k: layer_params(k, cfg))(lkeys)
        else:
            p["layers"] = [layer_params(k, cfg, i) for i, k in enumerate(lkeys)]
    return p


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Dense caches: per layer ``[B, max_len, heads, width]`` for each of
    ``cfg.cache_widths()`` (K and V, or MLA's one latent row)."""
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    kv_shape = (batch, max_len, cfg.n_kv_heads, hd)

    if cfg.family == "ssm":
        st = mamba2_init_state(cfg, batch, dt)
        return {
            "layers": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), st
            )
        }
    if cfg.family == "hybrid":
        st = mamba2_init_state(cfg, batch, dt)
        n_shared = cfg.n_layers // cfg.shared_attn_every
        return {
            "mamba": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), st
            ),
            "attn": {
                "k": jnp.zeros((n_shared,) + kv_shape, dt),
                "v": jnp.zeros((n_shared,) + kv_shape, dt),
            },
        }
    return {
        "layers": {
            name: jnp.zeros((cfg.n_layers, batch, max_len, heads, w), dt)
            for name, (heads, w) in cfg.cache_widths().items()
        }
    }


def init_paged_cache(
    cfg: ModelConfig, batch: int, num_pages: int, page_tokens: int
) -> Params:
    """Paged-KV cache: per-layer physical page pools ``[KV, num_pages+1, P,
    hd]``, kv-head-major (the +1 is the reserved TRASH page absorbing
    masked/out-of-range writes).  The per-slot page table is NOT part of this pytree — it is
    host-owned (``serving.kv_pool.KVPool``) and rides into each forward call
    as the ``page_table`` operand, so prefix-shared pages can be remapped
    between steps without touching device pools.

    SSM recurrent state is not paged (it is O(1) per slot, not O(seq));
    the hybrid family pages only its shared-attention KV."""
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    pool_shape = (cfg.n_kv_heads, num_pages + 1, page_tokens, hd)   # hybrid

    if cfg.family == "ssm":
        return init_cache(cfg, batch, 0)
    if cfg.family == "hybrid":
        st = mamba2_init_state(cfg, batch, dt)
        n_shared = cfg.n_layers // cfg.shared_attn_every
        return {
            "mamba": jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), st
            ),
            "attn": {
                "k": jnp.zeros((n_shared,) + pool_shape, dt),
                "v": jnp.zeros((n_shared,) + pool_shape, dt),
            },
        }
    return {
        "layers": {
            name: jnp.zeros((cfg.n_layers, heads, num_pages + 1, page_tokens, w), dt)
            for name, (heads, w) in cfg.cache_widths().items()
        }
    }


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------


def _embed_in(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Token or stub-frontend embedding + positions."""
    if cfg.frontend in ("patch_stub", "frame_stub"):
        x = batch["embeds"].astype(_dtype(cfg))
        b, s = x.shape[0], x.shape[1]
    else:
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0)
        b, s = tokens.shape
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.mrope_sections is not None:
        positions = batch.get("positions")
        if positions is None:
            pos1 = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            positions = jnp.stack([pos1, pos1, pos1])
        positions = positions.astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = shard_hint(x, "batch", None, "embed")
    return x, positions


def _logits_out(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rmsnorm(x, params["ln_final"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    logits = softcap(logits, cfg.logit_softcap)
    return shard_hint(logits, "batch", None, "vocab")


def _dense_stack(
    params, x, cfg, positions, caches, cache_pos, q_lens=None, page_table=None
):
    """Scan (or loop) over transformer layers; returns (x, new_caches, aux)."""
    windows = layer_windows(cfg)

    def one(x, layer_p, window, cache):
        # paged KV: the table is one [B, pages_per_slot] array shared by all
        # layers (each layer has its own pool, same page ids) — inject it at
        # the per-layer cache dict, strip it from the per-layer result so the
        # scan carry / stacked pytree holds only the cache arrays
        if cache is not None and page_table is not None:
            cache = dict(cache, table=page_table)
        x, nc, aux = block_apply(
            layer_p, x, cfg,
            positions=positions, window=window,
            kv_cache=cache, cache_pos=cache_pos, q_lens=q_lens,
        )
        if nc is not None and "table" in nc:
            nc = {name: a for name, a in nc.items() if name != "table"}
        return x, nc, aux

    if cfg.scan_layers:
        def body(x, xs):
            layer_p, window, cache = xs
            x, new_cache, aux = one(x, layer_p, window, cache)
            return x, (new_cache, aux)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        win = jnp.asarray(windows, jnp.int32)
        xs = (params["layers"], win, caches["layers"] if caches else None)
        if caches is None:
            xs = (params["layers"], win)

            def body_nc(x, xs):
                layer_p, window = xs
                x, _, aux = one(x, layer_p, window, None)
                return x, aux

            body_fn = jax.checkpoint(body_nc) if cfg.remat else body_nc
            x, auxs = jax.lax.scan(body_fn, x, xs)
            return x, None, auxs.sum()
        x, (new_caches, auxs) = jax.lax.scan(body_fn, x, xs)
        return x, {"layers": new_caches}, auxs.sum()

    # unrolled python loop (smoke / tiny configs / FD roofline compiles)
    new_layers = {name: [] for name in caches["layers"]} if caches else None
    aux_total = jnp.zeros((), jnp.float32)
    for i, layer_p in enumerate(params["layers"]):
        cache_i = (
            {name: a[i] for name, a in caches["layers"].items()}
            if caches
            else None
        )
        # the window is closed over, not an argument: it stays a static int
        # under remat as well
        one_fn = lambda x_, p_, c_, w=windows[i]: one(x_, p_, w, c_)
        if cfg.remat:
            one_fn = jax.checkpoint(one_fn)
        x, nc, aux = one_fn(x, layer_p, cache_i)
        aux_total = aux_total + aux
        if caches:
            for name, a in nc.items():
                new_layers[name].append(a)
    new_caches = (
        {"layers": {name: jnp.stack(a) for name, a in new_layers.items()}}
        if caches
        else None
    )
    return x, new_caches, aux_total


def _ssm_stack(params, x, cfg, caches, cache_pos=None, q_lens=None):
    # continuation (decode step OR a chunked-prefill chunk): the recurrent
    # state carries in — mamba2_block picks the single-token or the
    # chunk-continuation path from the sequence length.  cache_pos=None is
    # the fresh whole-prompt prefill (state starts at zero).
    cont = caches is not None and cache_pos is not None

    def one(x, layer_p, state):
        h = rmsnorm(x, layer_p["ln"], cfg.norm_eps)
        out, new_state = mamba2_block(
            layer_p["mamba"], h, cfg, state=state if cont else None,
            seq_lens=q_lens if cont else None,
        )
        return x + out, new_state

    if cfg.scan_layers:
        def body(x, xs):
            layer_p, state = xs
            x, new_state = one(x, layer_p, state)
            return x, new_state

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, new_states = jax.lax.scan(body_fn, x, (params["layers"], caches["layers"]))
        return x, {"layers": new_states}

    one_fn = jax.checkpoint(one) if cfg.remat else one
    new_states = []
    for i, layer_p in enumerate(params["layers"]):
        st = jax.tree.map(lambda a: a[i], caches["layers"]) if caches else None
        x, ns = one_fn(x, layer_p, st)
        new_states.append(ns)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
    return x, {"layers": stacked}


def _hybrid_stack(
    params, x, x_embed, cfg, positions, caches, cache_pos, q_lens=None,
    page_table=None,
):
    """Zamba2: mamba trunk in segments; shared attn block every N layers."""
    every = cfg.shared_attn_every
    n_shared = cfg.n_layers // every
    # cache_pos given = continuation (decode, or a chunked-prefill chunk):
    # mamba state carries across the boundary; None = fresh whole prefill
    cont = cache_pos is not None
    attn_pos = cache_pos if cache_pos is not None else jnp.zeros((), jnp.int32)

    def mamba_seg(x, seg_params, seg_states):
        def body(x, xs):
            layer_p, state = xs
            out, new_state = mamba2_block(
                layer_p["mamba"], rmsnorm(x, layer_p["ln"], cfg.norm_eps), cfg,
                state=state if cont else None,
                seq_lens=q_lens if cont else None,
            )
            return x + out, new_state

        body_fn = jax.checkpoint(body) if cfg.remat else body
        if cfg.scan_layers:
            return jax.lax.scan(body_fn, x, (seg_params, seg_states))
        # unrolled (FD roofline compiles need real per-layer HLO)
        outs = []
        for i in range(every):
            sl = jax.tree.map(lambda a: a[i], (seg_params, seg_states))
            x, ns = body_fn(x, sl)
            outs.append(ns)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)

    new_mamba, new_attn_k, new_attn_v = [], [], []
    for seg in range(n_shared):
        lo = seg * every
        seg_params = jax.tree.map(
            lambda a: jax.lax.slice_in_dim(a, lo, lo + every, axis=0),
            params["mamba_layers"],
        )
        seg_states = jax.tree.map(
            lambda a: jax.lax.slice_in_dim(a, lo, lo + every, axis=0),
            caches["mamba"],
        )
        x, seg_new_states = mamba_seg(x, seg_params, seg_states)
        new_mamba.append(seg_new_states)

        # shared attention block on concat(hidden, embedding) (Zamba design)
        u = jnp.concatenate([x, x_embed], axis=-1) @ params["shared_proj_in"]
        cache_i = (
            {"k": caches["attn"]["k"][seg], "v": caches["attn"]["v"][seg]}
            if cont or caches is not None
            else None
        )
        if cache_i is not None and page_table is not None:
            cache_i["table"] = page_table
        u, nc, _ = block_apply(
            params["shared_block"], u, cfg,
            positions=positions, window=1 << 30,
            kv_cache=cache_i, cache_pos=attn_pos, q_lens=q_lens,
        )
        x = x + u
        if nc is not None:
            new_attn_k.append(nc["k"])
            new_attn_v.append(nc["v"])

    new_caches = {
        "mamba": jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *new_mamba),
        "attn": (
            {"k": jnp.stack(new_attn_k), "v": jnp.stack(new_attn_v)}
            if new_attn_k
            else caches["attn"]
        ),
    }
    return x, new_caches


def forward(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    *,
    caches: Optional[Params] = None,
    cache_pos: Optional[jax.Array] = None,
    q_lens: Optional[jax.Array] = None,  # [B] valid tokens per row (fused
                                         # mixed prefill/decode batch)
    page_table: Optional[jax.Array] = None,  # [B, pages_per_slot] int32 —
                                             # paged-KV page table (−1 =
                                             # unmapped); caches hold pools
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (logits [B,S,V], new_caches, aux_loss)."""
    x, positions = _embed_in(params, batch, cfg)
    if cache_pos is not None:
        # decode: absolute positions offset by the cache fill level.  A [B]
        # cache_pos vector carries one depth per row (ragged batches): each
        # row's positions — and its causal mask / KV write index downstream —
        # follow its own fill level.
        cp = jnp.asarray(cache_pos, jnp.int32)
        if cp.ndim == 0:
            positions = positions + cp
        elif cfg.mrope_sections is not None:   # positions: [3, B, S]
            positions = positions + cp[None, :, None]
        else:                                  # positions: [B, S]
            positions = positions + cp[:, None]
        cache_pos = cp
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        if caches is None:
            caches = init_cache(cfg, x.shape[0], 0)
        x, new_caches = _ssm_stack(params, x, cfg, caches, cache_pos, q_lens)
    elif cfg.family == "hybrid":
        if caches is None:
            caches = init_cache(cfg, x.shape[0], x.shape[1])
        x, new_caches = _hybrid_stack(
            params, x, x, cfg, positions, caches, cache_pos, q_lens, page_table
        )
    else:
        x, new_caches, aux = _dense_stack(
            params, x, cfg, positions, caches, cache_pos, q_lens, page_table
        )
    logits = _logits_out(params, x, cfg)
    return logits, new_caches, aux


# --------------------------------------------------------------------------
# public entry points (what the launcher jits)
# --------------------------------------------------------------------------


def train_forward(params, batch, cfg: ModelConfig):
    logits, _, aux = forward(params, batch, cfg)
    return logits, aux


def prefill(params, batch, cfg: ModelConfig, max_len: Optional[int] = None):
    """Run the prompt, return (last_logits, caches)."""
    if cfg.frontend in ("patch_stub", "frame_stub"):
        b, s = batch["embeds"].shape[:2]
    else:
        b, s = batch["tokens"].shape
    max_len = max_len or s
    if cfg.family in ("ssm", "hybrid"):
        caches = init_cache(cfg, b, max_len)
        logits, caches, _ = forward(params, batch, cfg, caches=caches, cache_pos=None)
    else:
        caches = init_cache(cfg, b, max_len)
        logits, caches, _ = forward(
            params, batch, cfg, caches=caches, cache_pos=jnp.zeros((), jnp.int32)
        )
    return logits[:, -1], caches


def prefill_chunked(
    params,
    batch,
    cfg: ModelConfig,
    max_len: Optional[int] = None,
    *,
    chunk: int = 64,
    caches: Optional[Params] = None,
    page_table: Optional[jax.Array] = None,
    start: int = 0,
):
    """Chunked prefill: run the prompt in ``chunk``-token pieces, carrying
    the caches across chunk boundaries — greedy-token-identical to
    :func:`prefill`.

    Every family carries its state through the boundary: dense/MoE write
    each chunk's KV at its absolute offset (per-chunk positions offset by
    ``cache_pos``, so RoPE and the causal/sliding-window masks match the
    whole-prompt pass), SSM/hybrid thread the recurrent ssm state and the
    causal-conv tails (see :func:`repro.models.ssm.mamba2_block`).  This is
    the unit the serving engine's interleaved prefill state machine
    executes between ragged decode steps.

    ``caches``/``page_table`` continue an existing (possibly paged) cache
    instead of allocating dense rows; ``start`` skips the first ``start``
    prompt tokens — only sound when ``caches`` already hold their state
    (paged prefix reuse: shared pages mapped into this row's table; never
    sound for SSM/hybrid recurrent state, which pages don't capture)."""
    if cfg.frontend in ("patch_stub", "frame_stub"):
        b, s = batch["embeds"].shape[:2]
    else:
        b, s = batch["tokens"].shape
    if chunk <= 0:
        raise ValueError(f"chunk must be > 0, got {chunk}")
    if not 0 <= start < s:
        raise ValueError(f"start must be in [0, {s}), got {start}")
    max_len = max_len or s
    if caches is None:
        caches = init_cache(cfg, b, max_len)
    logits = None
    off = start
    while off < s:
        n = min(chunk, s - off)
        sub = dict(batch)
        for key in ("tokens", "embeds"):
            if key in sub:
                sub[key] = sub[key][:, off : off + n]
        logits, caches, _ = forward(
            params, sub, cfg, caches=caches,
            cache_pos=jnp.asarray(off, jnp.int32),
            page_table=page_table,
        )
        off += n
    return logits[:, -1], caches


def decode_step(
    params, token_batch, caches, cache_pos, cfg: ModelConfig, *, page_table=None
):
    """One-token step: token [B,1] (or embeds [B,1,D]); ``cache_pos`` is a
    scalar (all rows at one depth) or a ``(B,)`` int32 vector (ragged batch —
    per-row KV write index and causal mask over each row's valid length).
    ``page_table`` switches the KV write/read to the paged pools in
    ``caches``."""
    logits, new_caches, _ = forward(
        params, token_batch, cfg, caches=caches, cache_pos=cache_pos,
        page_table=page_table,
    )
    return logits[:, -1], new_caches


def fused_step(
    params, token_batch, caches, cache_pos, q_lens, cfg: ModelConfig,
    *, page_table=None,
):
    """One FUSED mixed prefill/decode step: tokens [B, S] where row b's first
    ``q_lens[b]`` tokens are valid — decode rows carry 1, prefill chunks up to
    S, idle rows 0.  ``cache_pos`` is a (B,) int32 vector of per-row depths.
    Rows write KV / advance SSM state only over their valid span; everything
    beyond is untouched.  Returns the FULL logits [B, S, V] (the caller reads
    row b at index q_lens[b]-1) and the new caches — one compiled program
    serves the whole serving step."""
    logits, new_caches, _ = forward(
        params, token_batch, cfg, caches=caches,
        cache_pos=jnp.asarray(cache_pos, jnp.int32),
        q_lens=jnp.asarray(q_lens, jnp.int32),
        page_table=page_table,
    )
    return logits, new_caches
