"""Per-architecture smoke tests (deliverable f): reduced config of each
family runs one forward + one train step on CPU; shapes + finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models.model import build_model, cross_entropy_loss, param_count
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.step import make_train_step

# ~70 s of jit compiles across 10 architectures; out of the fast tier
pytestmark = pytest.mark.slow

B, S = 2, 32


def make_batch(cfg, key):
    if cfg.frontend == "patch_stub":
        batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)}
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            batch["positions"] = jnp.stack([pos, pos, pos])
    elif cfg.frontend == "frame_stub":
        batch = {
            "frames": jax.random.normal(key, (B, S, cfg.d_model), jnp.float32),
            "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        }
    else:
        batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    assert param_count(params) > 0
    batch = make_batch(cfg, key)
    logits, aux = model.train_forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    opt = init_opt_state(params)
    step = make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=1))
    batch = make_batch(cfg, key)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_opt["step"]) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        params, new_params,
    )
    assert max(jax.tree.leaves(moved)) > 0.0


@pytest.mark.parametrize(
    "arch",
    [
        "llama3.2-1b", "gemma2-27b", "qwen3-14b", "gemma-7b", "arctic-480b",
        "qwen2-moe-a2.7b", "mamba2-130m", "zamba2-2.7b", "seamless-m4t-large-v2",
        "moonlight-16b-a3b",
    ],
)
def test_prefill_decode_matches_teacher_forcing(arch):
    cfg = get_config(arch).smoke()
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    s = 16
    toks = jax.random.randint(key, (B, s), 0, cfg.vocab_size)
    full = {"tokens": toks}
    if cfg.family == "encdec":
        full["frames"] = jax.random.normal(key, (B, s, cfg.d_model), jnp.float32)
    logits_full, _ = model.train_forward(params, full)
    ref = np.asarray(logits_full[:, -1], np.float32)

    pre = dict(full)
    pre["tokens"] = toks[:, : s - 1]
    _, caches = model.prefill(params, pre, s)
    logits_dec, _ = model.decode_step(
        params, {"tokens": toks[:, s - 1 : s]}, caches, jnp.asarray(s - 1, jnp.int32)
    )
    got = np.asarray(logits_dec, np.float32)
    err = np.abs(ref - got).max() / (np.abs(ref).max() + 1e-9)
    assert err < 2e-2, err


def test_gemma2_sliding_window_masks_old_tokens():
    """A local-attention layer must ignore tokens beyond the window."""
    cfg = get_config("gemma2-27b").smoke()
    from dataclasses import replace

    cfg = replace(cfg, n_layers=1, local_global_pattern="L", sliding_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    s = 12
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, s), 0, cfg.vocab_size)
    # perturb a token OUTSIDE the final position's window: logits at -1 unchanged
    t2 = t1.at[0, 2].set((t1[0, 2] + 1) % cfg.vocab_size)
    l1, _ = model.train_forward(params, {"tokens": t1})
    l2, _ = model.train_forward(params, {"tokens": t2})
    np.testing.assert_allclose(
        np.asarray(l1[0, -1]), np.asarray(l2[0, -1]), rtol=1e-5, atol=1e-5
    )
    # ...and a token INSIDE the window does change them
    t3 = t1.at[0, s - 2].set((t1[0, s - 2] + 1) % cfg.vocab_size)
    l3, _ = model.train_forward(params, {"tokens": t3})
    assert np.abs(np.asarray(l1[0, -1]) - np.asarray(l3[0, -1])).max() > 1e-6


def test_moe_padding_experts_never_selected():
    from repro.models.moe import router_topk

    cfg = get_config("qwen2-moe-a2.7b")  # FULL config: 60 real, 64 padded
    assert cfg.n_experts_padded > cfg.n_experts
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (64, cfg.d_model))
    router = jax.random.normal(key, (cfg.d_model, cfg.n_experts_padded))
    w, e, aux = router_topk(router, x, cfg)
    assert int(jnp.max(e)) < cfg.n_experts
