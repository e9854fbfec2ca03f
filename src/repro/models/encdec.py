"""Encoder–decoder backbone for seamless-m4t-large-v2 (audio family).

Per the assignment, the modality frontend is a STUB: ``input_specs()``
provides precomputed speech *frame embeddings* [B, S_enc, D].  We implement
the transformer backbone: a non-causal self-attention encoder and a causal
decoder with cross-attention.  At prefill the per-layer cross K/V are
computed once from the encoder memory and cached (standard enc-dec serving).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel.context import shard_hint
from .layers import (
    Params,
    attention_params,
    dense_init,
    embed_init,
    mlp,
    mlp_params,
    multihead_attention,
    rmsnorm,
    rmsnorm_init,
)


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _enc_layer_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "ln_attn": rmsnorm_init(cfg.d_model),
        "attn": attention_params(ks[0], cfg, _dtype(cfg)),
        "ln_mlp": rmsnorm_init(cfg.d_model),
        "mlp": mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.activation, _dtype(cfg)),
    }


def _dec_layer_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "ln_self": rmsnorm_init(cfg.d_model),
        "self_attn": attention_params(ks[0], cfg, _dtype(cfg)),
        "ln_cross": rmsnorm_init(cfg.d_model),
        "cross_attn": attention_params(ks[1], cfg, _dtype(cfg)),
        "ln_mlp": rmsnorm_init(cfg.d_model),
        "mlp": mlp_params(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, _dtype(cfg)),
    }


def init_params(key, cfg: ModelConfig) -> Params:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 6)
    ekeys = jax.random.split(keys[0], cfg.n_encoder_layers)
    dkeys = jax.random.split(keys[1], cfg.n_layers)
    p: Params = {
        "embed": embed_init(keys[2], cfg.vocab_size, cfg.d_model, dt),
        "ln_final": rmsnorm_init(cfg.d_model),
        "ln_enc_final": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[3], cfg.d_model, cfg.vocab_size, dt)
    if cfg.scan_layers:
        p["encoder"] = jax.vmap(lambda k: _enc_layer_params(k, cfg))(ekeys)
        p["decoder"] = jax.vmap(lambda k: _dec_layer_params(k, cfg))(dkeys)
    else:
        p["encoder"] = [_enc_layer_params(k, cfg) for k in ekeys]
        p["decoder"] = [_dec_layer_params(k, cfg) for k in dkeys]
    return p


# --------------------------------------------------------------------------


def _enc_block(layer_p, x, cfg, positions):
    h = rmsnorm(x, layer_p["ln_attn"])
    out, _ = multihead_attention(
        layer_p["attn"], h, cfg, positions=positions, causal=False
    )
    x = x + out
    h = rmsnorm(x, layer_p["ln_mlp"])
    return x + mlp(layer_p["mlp"], h, cfg.activation)


def encode(params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """frames: [B, S_enc, D] (stub frontend output) → memory [B, S_enc, D]."""
    x = frames.astype(_dtype(cfg))
    x = shard_hint(x, "batch", None, "embed")
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cfg.scan_layers:
        def body(x, layer_p):
            return _enc_block(layer_p, x, cfg, positions), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body_fn, x, params["encoder"])
    else:
        blk = (
            jax.checkpoint(partial(_enc_block, cfg=cfg, positions=positions))
            if cfg.remat
            else partial(_enc_block, cfg=cfg, positions=positions)
        )
        for layer_p in params["encoder"]:
            x = blk(layer_p, x)
    return rmsnorm(x, params["ln_enc_final"])


def _cross_kv(layer_p, memory, cfg) -> Tuple[jax.Array, jax.Array]:
    hd = cfg.resolved_head_dim
    b, s = memory.shape[:2]
    k = (memory @ layer_p["cross_attn"]["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (memory @ layer_p["cross_attn"]["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    return k, v


def _dec_block(layer_p, x, cfg, positions, memory_kv, self_cache, cache_pos,
               q_lens=None, page_table=None):
    # paged self-attn KV: per-layer page pools + one shared [B, pps] table
    # (cross K/V stays dense — it is encoder-length, written once, never grows)
    if self_cache is not None and page_table is not None:
        self_cache = dict(self_cache, table=page_table)
    h = rmsnorm(x, layer_p["ln_self"])
    out, new_cache = multihead_attention(
        layer_p["self_attn"], h, cfg,
        positions=positions, kv_cache=self_cache, cache_pos=cache_pos,
        q_lens=q_lens,
    )
    if new_cache is not None and "table" in new_cache:
        new_cache = {"k": new_cache["k"], "v": new_cache["v"]}
    x = x + out
    h = rmsnorm(x, layer_p["ln_cross"])
    # cross-attn sees the full encoder memory regardless of row length;
    # q_lens only zeroes the padding query rows for determinism
    out, _ = multihead_attention(
        layer_p["cross_attn"], h, cfg, positions=positions, cross_kv=memory_kv,
        q_lens=q_lens,
    )
    x = x + out
    h = rmsnorm(x, layer_p["ln_mlp"])
    return x + mlp(layer_p["mlp"], h, cfg.activation), new_cache


def decode_stack(params, tokens, cfg, memory=None, cross_cache=None,
                 self_cache=None, cache_pos=None, q_lens=None,
                 page_table=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard_hint(x, "batch", None, "embed")
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if cache_pos is not None:
        # scalar: one depth for every row; [B] vector: ragged batch — each
        # row offsets (and masks, and writes KV) at its own fill level
        cp = jnp.asarray(cache_pos, jnp.int32)
        positions = positions + (cp if cp.ndim == 0 else cp[:, None])
        cache_pos = cp

    if cfg.scan_layers:
        def body(x, xs):
            if cross_cache is not None:
                layer_p, sc, ck, cv = xs
                kv = (ck, cv)
            else:
                layer_p, sc = xs[0], xs[1]
                kv = _cross_kv(layer_p, memory, cfg)
            x, nc = _dec_block(layer_p, x, cfg, positions, kv, sc, cache_pos,
                               q_lens, page_table)
            return x, nc

        body_fn = jax.checkpoint(body) if cfg.remat else body
        if cross_cache is not None:
            xs = (params["decoder"], self_cache, cross_cache["k"], cross_cache["v"])
        else:
            xs = (params["decoder"], self_cache)
        x, new_self = jax.lax.scan(body_fn, x, xs)
    else:
        dec_fn = jax.checkpoint(_dec_block, static_argnums=(2,)) if cfg.remat else _dec_block
        new_k, new_v = [], []
        for i, layer_p in enumerate(params["decoder"]):
            if cross_cache is not None:
                kv = (cross_cache["k"][i], cross_cache["v"][i])
            else:
                kv = _cross_kv(layer_p, memory, cfg)
            sc = (
                jax.tree.map(lambda a: a[i], self_cache)
                if self_cache is not None
                else None
            )
            x, nc = dec_fn(layer_p, x, cfg, positions, kv, sc, cache_pos,
                           q_lens, page_table)
            if nc is not None:
                new_k.append(nc["k"])
                new_v.append(nc["v"])
        new_self = (
            {"k": jnp.stack(new_k), "v": jnp.stack(new_v)} if new_k else None
        )
    x = rmsnorm(x, params["ln_final"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = shard_hint(x @ head, "batch", None, "vocab")
    return logits, new_self


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------


def train_forward(params, batch, cfg: ModelConfig):
    memory = encode(params, batch["frames"], cfg)
    logits, _ = decode_stack(params, batch["tokens"], cfg, memory=memory)
    return logits, jnp.zeros((), jnp.float32)


def init_self_cache(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_self_cache(cfg: ModelConfig, num_pages: int, page_tokens: int):
    """Paged decoder self-attention cache: per-layer page pools (last page is
    the reserved trash page); the page table is passed per call."""
    hd = cfg.resolved_head_dim
    dt = _dtype(cfg)
    shape = (cfg.n_layers, cfg.n_kv_heads, num_pages + 1, page_tokens, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def prefill(params, batch, cfg: ModelConfig, max_len: Optional[int] = None):
    """Encode + teacher-forced prompt pass; returns (last_logits, caches)."""
    memory = encode(params, batch["frames"], cfg)
    # cross K/V computed once per layer
    if cfg.scan_layers:
        ck, cv = jax.vmap(lambda lp: _cross_kv(lp, memory, cfg))(params["decoder"])
    else:
        kvs = [_cross_kv(lp, memory, cfg) for lp in params["decoder"]]
        ck = jnp.stack([k for k, _ in kvs])
        cv = jnp.stack([v for _, v in kvs])
    cross_cache = {"k": ck, "v": cv}
    b, s = batch["tokens"].shape
    self_cache = init_self_cache(cfg, b, max_len or s)
    logits, new_self = decode_stack(
        params, batch["tokens"], cfg,
        cross_cache=cross_cache, self_cache=self_cache,
        cache_pos=jnp.zeros((), jnp.int32),
    )
    return logits[:, -1], {"self": new_self, "cross": cross_cache}


def prefill_chunked(
    params,
    batch,
    cfg: ModelConfig,
    max_len: Optional[int] = None,
    *,
    chunk: int = 64,
    self_cache=None,
    page_table=None,
    start: int = 0,
):
    """Chunked decoder prefill: the encoder runs once (cross K/V cached as
    in :func:`prefill`), then the decoder prompt is teacher-forced in
    ``chunk``-token pieces with the self-attention cache carried across
    boundaries — greedy-token-identical to the whole-prompt pass.

    ``self_cache``/``page_table`` continue an existing (possibly paged)
    self-attention cache; ``start`` skips prompt tokens whose KV the mapped
    pages already hold (prefix reuse — sound here because cross K/V and the
    decoder self cache are the decoder's only state)."""
    if chunk <= 0:
        raise ValueError(f"chunk must be > 0, got {chunk}")
    memory = encode(params, batch["frames"], cfg)
    if cfg.scan_layers:
        ck, cv = jax.vmap(lambda lp: _cross_kv(lp, memory, cfg))(params["decoder"])
    else:
        kvs = [_cross_kv(lp, memory, cfg) for lp in params["decoder"]]
        ck = jnp.stack([k for k, _ in kvs])
        cv = jnp.stack([v for _, v in kvs])
    cross_cache = {"k": ck, "v": cv}
    b, s = batch["tokens"].shape
    if not 0 <= start < s:
        raise ValueError(f"start must be in [0, {s}), got {start}")
    if self_cache is None:
        self_cache = init_self_cache(cfg, b, max_len or s)
    logits = None
    off = start
    while off < s:
        n = min(chunk, s - off)
        logits, self_cache = decode_stack(
            params, batch["tokens"][:, off : off + n], cfg,
            cross_cache=cross_cache, self_cache=self_cache,
            cache_pos=jnp.asarray(off, jnp.int32),
            page_table=page_table,
        )
        off += n
    return logits[:, -1], {"self": self_cache, "cross": cross_cache}


def decode_step(
    params, token_batch, caches, cache_pos, cfg: ModelConfig, *, page_table=None
):
    """One-token decoder step; ``cache_pos`` is a scalar or a ``(B,)`` int32
    vector (ragged batch — per-row self-attention cache depth)."""
    logits, new_self = decode_stack(
        params, token_batch["tokens"], cfg,
        cross_cache=caches["cross"], self_cache=caches["self"],
        cache_pos=cache_pos, page_table=page_table,
    )
    return logits[:, -1], {"self": new_self, "cross": caches["cross"]}


def fused_step(
    params, token_batch, caches, cache_pos, q_lens, cfg: ModelConfig,
    *, page_table=None,
):
    """One FUSED mixed prefill/decode decoder step (see
    :func:`repro.models.transformer.fused_step`): tokens [B, S], per-row
    ``(cache_pos, q_lens)``; returns the FULL logits [B, S, V] and new caches."""
    logits, new_self = decode_stack(
        params, token_batch["tokens"], cfg,
        cross_cache=caches["cross"], self_cache=caches["self"],
        cache_pos=jnp.asarray(cache_pos, jnp.int32),
        q_lens=jnp.asarray(q_lens, jnp.int32),
        page_table=page_table,
    )
    return logits, {"self": new_self, "cross": caches["cross"]}
