"""The main-path flash kernels compile for a TPU v5e, at llama3.2-1b widths and
at the benchmark configs' widths (Qwen3-14B, Qwen1.5-MoE, and the latent
kernel at Moonlight-16B-A3B's).

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (block shapes that break
the (8, 128) tiling rule, too much VMEM).  Interpret mode never runs here:
``interpret=False`` is passed explicitly, and every compile must hold the
kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
the test process that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention, flash_attention_paged

CFG = get_config("llama3.2-1b")
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
B, MAX_LEN, PAGE = 8, 2048, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("sq", [1, 5, 64])
def test_dense_flash_kernel_compiles_for_v5e(one_chip, sq):
    step = jax.jit(lambda q, k, v, qp, ql: flash_attention(
        q, k, v, qp, None, ql, scale=D ** -0.5, interpret=False))
    compiled = step.lower(
        _spec(one_chip, (B, sq, H, D)),
        _spec(one_chip, (B, MAX_LEN, KV, D)),
        _spec(one_chip, (B, MAX_LEN, KV, D)),
        _spec(one_chip, (B, sq), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("sq", [1, 64])
def test_paged_flash_kernel_compiles_for_v5e(one_chip, sq):
    num_pages = B * MAX_LEN // PAGE
    pool = (KV, num_pages + 1, PAGE, D)          # kv-head-major, + trash page
    step = jax.jit(lambda q, pk, pv, tbl, qp, ql: flash_attention_paged(
        q, pk, pv, tbl, qp, ql, scale=D ** -0.5, interpret=False))
    compiled = step.lower(
        _spec(one_chip, (B, sq, H, D)),
        _spec(one_chip, pool),
        _spec(one_chip, pool),
        _spec(one_chip, (B, MAX_LEN // PAGE), jnp.int32),
        _spec(one_chip, (B, sq), jnp.int32),
        _spec(one_chip, (B,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("paged", [False, True])
def test_kernel_keeps_its_name_under_a_renamed_wrapper(one_chip, paged):
    """The trace names each kernel call after its ``pallas_call`` name
    (``flash_attention`` / ``flash_attention_paged``), not after the jitted
    wrapper around it, so renaming the wrapper cannot rename the kernel."""
    if paged:
        num_pages = B * MAX_LEN // PAGE
        pool = (KV, num_pages + 1, PAGE, D)

        def renamed_wrapper(q, pk, pv, tbl, qp, ql):
            return flash_attention_paged.__wrapped__(
                q, pk, pv, tbl, qp, ql, scale=D ** -0.5, interpret=False)

        args = [(B, 64, H, D), pool, pool, ((B, MAX_LEN // PAGE), jnp.int32),
                ((B, 64), jnp.int32), ((B,), jnp.int32)]
    else:
        def renamed_wrapper(q, k, v, qp, ql):
            return flash_attention.__wrapped__(
                q, k, v, qp, None, ql, scale=D ** -0.5, interpret=False)

        args = [(B, 5, H, D), (B, MAX_LEN, KV, D), (B, MAX_LEN, KV, D),
                ((B, 5), jnp.int32), ((B,), jnp.int32)]
    specs = [_spec(one_chip, *a) if isinstance(a[0], tuple) else _spec(one_chip, a)
             for a in args]
    text = jax.jit(renamed_wrapper).lower(*specs).compile().as_text()
    kernels = re.findall(r"%([\w.]+) = \S+ custom-call\(.*tpu_custom_call", text)
    want = "flash_attention_paged" if paged else "flash_attention"
    assert kernels and all(re.sub(r"\.\d+$", "", k) == want for k in kernels)


@pytest.mark.parametrize("arch, slots, pages, sq, rows", [
    ("qwen3-14b", 16, 40, 1, 8),       # 40/8 heads: 5 of 8 rows valid
    ("qwen3-14b", 16, 40, 64, 320),    # one 320-row query block
    ("qwen2-moe-a2.7b", 6, 128, 64, 64),   # 16/16 heads: rep 1, unstacked
])
def test_paged_flash_kernel_stacks_the_gqa_group(one_chip, arch, slots, pages,
                                                 sq, rows):
    """At a benchmark config's widths the paged kernel compiles with one
    grid row per (slot, KV head), the group's query heads stacked in its
    query block (the vector masks compare positions scaled by ``rep``), and
    keeps its name."""
    cfg = get_config(arch)
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pool = (kv, slots * pages + 1, PAGE, d)
    step = jax.jit(lambda q, pk, pv, tbl, qp, ql: flash_attention_paged(
        q, pk, pv, tbl, qp, ql, scale=d ** -0.5, interpret=False))
    text = step.lower(
        _spec(one_chip, (slots, sq, h, d)),
        _spec(one_chip, pool),
        _spec(one_chip, pool),
        _spec(one_chip, (slots, pages), jnp.int32),
        _spec(one_chip, (slots, sq), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32),
    ).compile().as_text()
    kernels = re.findall(
        r"%([\w.]+) = (\w+\[[\d,]+\])\S* custom-call\(.*tpu_custom_call", text)
    assert [(re.sub(r"\.\d+$", "", n), s) for n, s in kernels] == [
        ("flash_attention_paged", f"bf16[{slots * kv},{rows},{d}]")]


@pytest.mark.parametrize("sq, rows", [(1, 16), (64, 1024)])
def test_latent_kernel_compiles_at_moonlight_widths(one_chip, sq, rows):
    """MLA's absorbed kernel at the Moonlight cell's shapes (32 slots of
    128 pages, 16 heads on one latent pool of 640-lane rows, V its leading
    512 lanes): one grid row per slot, the heads stacked in the query
    block, under its own name, with no copy of the pool."""
    cfg = get_config("moonlight-16b-a3b")
    slots, pages, w = 32, 128, cfg.latent_cache_width
    step = jax.jit(lambda q, pool, tbl, qp, ql: flash_attention_paged(
        q, pool, pool, tbl, qp, ql, scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
        v_dim=cfg.kv_lora_rank, interpret=False))
    compiled = step.lower(
        _spec(one_chip, (slots, sq, cfg.n_heads, w)),
        _spec(one_chip, (1, slots * pages + 1, PAGE, w)),
        _spec(one_chip, (slots, pages), jnp.int32),
        _spec(one_chip, (slots, sq), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32),
    ).compile()
    kernels = re.findall(
        r"%([\w.]+) = (\w+\[[\d,]+\])\S* custom-call\(.*tpu_custom_call",
        compiled.as_text())
    assert [(re.sub(r"\.\d+$", "", n), s) for n, s in kernels] == [
        ("flash_attention_paged_latent", f"bf16[{slots},{rows},{cfg.kv_lora_rank}]")]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
