"""The plain reference: the published architecture's forward pass in float32
``jax.numpy``, written from the configuration file alone.  It imports
nothing of the program.  It reads the weights the benchmark made, one layer
at a time (cast to float32 inside that layer's call), so that the bf16 model
and its float32 copy never sit on the chip together.

The decoder layer is the architecture's own, in the family module that the
file's ``model_type`` names (``bench/families/<model_type>.py``).  What
every family shares is here: float8 rounding, the linear layer, RMSNorm,
RoPE, the embedding, the head in blocks of columns, and the comparison.

Every matmul runs at ``Precision.HIGHEST``.  ``quant=True`` is the control
of the check: the same forward with every linear layer's weights (per output
channel) and inputs (per token) rounded to float8 e4m3, the precision below
the served bf16.

The reading compared (see ``gaps``): teacher-forced over a request's prompt
and served tokens, at every position that served a token, how far the
reference's logit of that token lies below the reference's best.
"""

from __future__ import annotations

import json
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import family

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest float8 e4m3fn value
Q_BLOCK = 512           # query rows per attention block
V_BLOCK = 16384         # head columns per block


def q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, quant):
    """x [..., din] @ w [din, dout] in float32."""
    w = w.astype(jnp.float32)
    if quant:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, w_offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w_offset.astype(jnp.float32))


def rope(x, theta):
    """Rotate-half RoPE at positions 0..L-1; x [L, heads, hd]."""
    l, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("layer", "conf_json", "quant"))
def _layer(p, x, layer, conf_json, quant):
    """The family's ``layer``; the configuration goes in as JSON text, which
    ``jax.jit`` can hash."""
    return layer(p, x, json.loads(conf_json), quant)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_block(ln_final, head, x, eps, quant):
    return linear(rmsnorm(x, ln_final, eps), head, quant)


def _logits(ln_final, head, x, eps, quant):
    """x [n, d] @ head [d, V], in blocks of columns: a float32 copy of a
    whole 150k-row head would not fit beside the bf16 model."""
    v = head.shape[1]
    return jnp.concatenate([
        _logits_block(ln_final, head[:, i:i + V_BLOCK], x, eps, quant)
        for i in range(0, v, V_BLOCK)
    ], axis=-1)


def _to(x, tree):
    """``x`` moved to the device that holds ``tree`` (a pipeline's layers
    sit on several chips)."""
    (dev,) = jax.tree.leaves(tree)[0].devices()
    return jax.device_put(x, dev)


def hidden(params, conf: Dict, tokens: np.ndarray, quant: bool) -> jax.Array:
    """Final hidden states [L, d] of one padded token sequence."""
    conf_json, layer = json.dumps(conf, sort_keys=True), family(conf).layer
    x = _embed(params["embed"], _to(jnp.asarray(tokens), params["embed"]))
    for lp in params["layers"]:
        x = _layer(lp, _to(x, lp), layer, conf_json, quant)
    return _to(x, params["ln_final"])


def gaps(params, conf: Dict, seqs: Sequence[tuple], pad_to: int,
         out_max: int, control: bool = False) -> Dict[str, List[float]]:
    """Teacher-forced over each ``(prompt, served)`` pair: per served token,
    the reference's best logit minus its logit of that token (``served``);
    with ``control``, the same for the token the float8 forward ranks first
    at that position (``control``)."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    eps = conf["rms_norm_eps"]
    out: Dict[str, List[float]] = {"served": [], "control": []}
    for prompt, served in seqs:
        full = list(prompt) + list(served[:-1])
        toks = np.zeros(pad_to, np.int32)
        toks[: len(full)] = full
        rows = np.zeros(out_max, np.int32)
        n = len(served)
        rows[:n] = len(prompt) - 1 + np.arange(n)
        ref = _logits(params["ln_final"], head,
                      hidden(params, conf, toks, False)[rows], eps, False)
        best = jnp.max(ref, axis=-1)
        tgt = np.zeros(out_max, np.int32)
        tgt[:n] = served
        got = jnp.take_along_axis(ref, jnp.asarray(tgt)[:, None], axis=-1)[:, 0]
        out["served"].extend(np.asarray(best - got)[:n].tolist())
        if control:
            ctl = _logits(params["ln_final"], head,
                          hidden(params, conf, toks, True)[rows], eps, True)
            pick = jnp.argmax(ctl, axis=-1)
            at = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            out["control"].extend(np.asarray(best - at)[:n].tolist())
    return out
