"""The ``deepseek_v3`` family (``bench/families/deepseek_v3.py``) and the
Moonlight-16B-A3B cell: the served path (paged latent cache, absorbed
latent kernel) against the family's published-form reference; its counts at
the published widths; a whole tiny run; the cell and its metrics as data."""

import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import devtrace, loop, model, reference, run, spec
from bench.tests.test_bench_faults import half_batch_left_out, state_unchanged

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
CELL = "moonlight-16b-a3b-5L.longdoc-closed-32"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TINY = json.loads((HERE / "data" / "tiny-mla.json").read_text())
MOONLIGHT = json.loads((REPO / "bench" / "configs" / "moonlight-16b-a3b-5L.json").read_text())


def served_logits(conf, params, prompts, extra, chunk=16, page=16):
    """Logits of the program's stage executor (plan-free, one stage): each
    prompt prefilled in ``chunk``-token fused steps into the paged latent
    pools, then ``extra`` teacher-forced decode steps; [slot, position, V]
    for the positions of ``prompt + extra`` tokens."""
    from repro.serving.stage_executor import Stage, StageExecutor

    cfg = model.program_config(conf)
    dev = jax.devices()[0]
    ex = StageExecutor(cfg, params, [Stage(dev, list(range(cfg.n_layers)), True, True)])
    slots, pps = len(prompts), 8
    caches = ex.init_paged_caches(slots * pps, page)
    table = np.arange(slots * pps, dtype=np.int32).reshape(slots, pps)
    seqs = [list(p) + list(e) for p, e in zip(prompts, extra)]
    out = [[] for _ in prompts]
    pos = np.zeros(slots, np.int32)
    while any(pos[b] < len(s) for b, s in enumerate(seqs)):
        prefill = any(pos[b] < len(p) for b, p in enumerate(prompts))
        width = chunk if prefill else 1
        toks = np.zeros((slots, chunk), np.int32)
        q = np.zeros(slots, np.int32)
        for b, s in enumerate(seqs):
            n = min(width, len(prompts[b]) - pos[b]) if pos[b] < len(prompts[b]) else (
                0 if prefill or pos[b] >= len(s) else 1)
            toks[b, :n] = s[pos[b]:pos[b] + n]
            q[b] = n
        logits, caches = ex.forward(jnp.asarray(toks), caches, cache_pos=pos.copy(),
                                    kind="fused", q_lens=jnp.asarray(q),
                                    page_table=table)
        logits = np.asarray(logits, np.float32)
        for b in range(slots):
            out[b].extend(logits[b, :q[b]])
        pos += q
    return [np.stack(o) for o in out]


def test_served_logits_match_the_published_reference():
    """Prefill then decode through the paged latent cache (absorbed kernel,
    interpret mode) gives the published form's full-forward logits.  In
    float32 the two differ only by summation order (2e-7 of the logits'
    range here): 1e-4 of the range; the float8 control's forward misses by
    ten times that and more (5e-2 here)."""
    conf = dict(TINY, torch_dtype="float32")
    held = model.experts_held(conf, model.program_config(conf))
    dev = jax.devices()[0]
    params = model.make_params(conf, held, 2**33 + 17, lambda p: dev)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).tolist() for n in (40, 23, 57)]
    extra = [rng.integers(0, 512, n).tolist() for n in (6, 9, 3)]
    got = served_logits(conf, params, prompts, extra)
    head = params["lm_head"]
    eps = conf["rms_norm_eps"]
    for quant in (False, True):
        worst = 0.0
        for b, (p, e) in enumerate(zip(prompts, extra)):
            toks = np.zeros(512, np.int32)
            n = len(p) + len(e)
            toks[:n] = p + e
            h = reference.hidden(params, conf, toks, quant)[:n]
            want = np.asarray(reference._logits(params["ln_final"], head, h, eps, quant))
            span = np.ptp(want)
            worst = max(worst, float(np.max(np.abs(got[b] - want))) / span)
        if quant:
            assert worst > 10 * 1e-4, worst
        else:
            assert worst < 1e-4, worst


def test_the_stage_caches_one_latent_pool_a_layer():
    """The stage program's cache: one ``[1, pages + 1, P, 128]`` pool of
    ``[latent, rope]`` rows a layer, no K or V; the stage runs the latent
    kernel, and no array of the step is a per-head K or V over the cached
    context."""
    from repro.serving.stage_executor import Stage, StageExecutor

    cfg = model.program_config(TINY)
    dev = jax.devices()[0]
    params = model.make_params(TINY, cfg.n_experts, 5, lambda p: dev)
    ex = StageExecutor(cfg, params, [Stage(dev, [0, 1, 2], True, True)])
    caches = ex.init_paged_caches(24, 16)
    assert [list(c) for c in caches[0]] == [["kv"]] * 3
    assert {c["kv"].shape for c in caches[0]} == {(1, 25, 16, 128)}
    table = np.arange(24, dtype=np.int32).reshape(3, 8)
    traced = ex.trace_stage(0, jnp.zeros((3, 16), jnp.int32), caches, np.zeros(3, np.int32),
                            q_lens=np.full(3, 16, np.int32), page_table=table)
    text = str(traced.jaxpr)
    assert "flash_attention_paged_latent" in text
    ctx, h = 8 * 16, cfg.n_heads
    shapes = [v.aval.shape for e in traced.jaxpr.eqns for v in e.outvars
              if hasattr(v.aval, "shape")]
    assert not [s for s in shapes if len(s) == 4 and tuple(s[1:3]) in ((ctx, h), (h, ctx))]


def test_counts_at_the_published_widths():
    conf = MOONLIGHT
    cfg = model.program_config(conf)
    held = model.experts_held(conf, cfg)
    assert held == 64 and cfg.first_k_dense == 1 and cfg.norm_eps == 1e-5
    from repro.models import transformer

    tree = jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0))
    program = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert model.param_count(conf, held) == program
    assert program == 3_093_455_616              # 3.09 B: 6.19 GB in bf16
    assert model.kv_bytes_per_token(conf) == 5 * 1152
    d, v = 2048, 163840
    attn = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    moe = d * 64 + 8 * 3 * d * 1408
    assert model.active_matmul_params(conf) == 5 * attn + 3 * d * 11264 + 4 * moe + d * v
    q_lens = np.array([64, 1, 0], np.int32)
    cache_pos = np.array([0, 100, 5], np.int32)
    ctx = 64 * 65 / 2 + 101                       # keys seen by the valid queries
    flops, nbytes = model.attention_call(conf, q_lens, cache_pos)
    assert flops == 16 * ctx * 2 * (576 + 512)
    assert nbytes == 2 * (65 * 16 * (576 + 512) + (64 + 101) * 576)
    assert model.step_flops(conf, q_lens, cache_pos) == \
        2.0 * model.active_matmul_params(conf) * 65 + 2.0 * 5 * 16 * (192 + 128) * ctx


def test_the_file_keeps_every_catalog_key():
    """Every published key of the catalog row, with only the depth cut."""
    published = {
        "first_k_dense_replace": 1, "hidden_size": 2048, "intermediate_size": 11264,
        "kv_lora_rank": 512, "max_position_embeddings": 8192, "model_type": "deepseek_v3",
        "moe_intermediate_size": 1408, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 6, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    assert {k: MOONLIGHT[k] for k in published} == published
    assert MOONLIGHT["num_hidden_layers"] == 5 and MOONLIGHT["reduced"] == ["num_hidden_layers"]


@pytest.fixture
def mla_root(tmp_path):
    """A checkout holding the repo's readers and families, the tiny MLA
    configuration under a closed mix, and the Moonlight cell's metrics."""
    bench = tmp_path / "bench"
    for sub in ("metrics", "families"):
        shutil.copytree(REPO / "bench" / sub, bench / sub)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    shutil.copy(HERE / "data" / "tiny-mla.json", bench / "configs" / "tiny-mla.json")
    shutil.copy(HERE / "data" / "tiny-closed.json", bench / "traffic" / "tiny-closed.json")
    bj = json.loads((REPO / "BENCHMARK.json").read_text())
    bj["workloads"] = [{"name": "tiny-mla.tiny-closed", "config": "tiny-mla",
                        "traffic": "tiny-closed", "chips": 1, "why": "CPU test"}]
    for key in ("end_to_end", "per_layer"):
        bj[key] = [dict(m, workloads=["tiny-mla.tiny-closed"]) for m in bj[key]
                   if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    return tmp_path


def altered(eng):
    inner, calls = eng.executor.forward, [0]

    def forward(tokens, caches=None, cache_pos=None, **kw):
        logits, new = inner(tokens, caches, cache_pos, **kw)
        calls[0] += 1
        if calls[0] % 3 == 0:
            logits = logits.at[..., 7].set(jnp.asarray(1e4, logits.dtype))
        return logits, new

    eng.executor.forward = forward


@pytest.mark.parametrize("fault,correct", [
    (None, True), (altered, False), (state_unchanged, False), (half_batch_left_out, False)])
def test_a_tiny_run_is_correct_only_when_sound(mla_root, fault, correct):
    """Planner → engine → stage executor → latent kernel on the CPU, checked
    against the published-form reference."""
    cell = spec.load_cell(mla_root, "tiny-mla.tiny-closed")
    out = run.run_cell(cell, 2**31 + 211, 4.0, False, jax.devices()[:1], PEAK,
                       t_start=time.perf_counter(), fault=fault)
    assert out["correct"] is correct, out["check"]
    assert out["check"]["tokens_compared"]["value"] > 0
    if correct:
        assert out["failed"] == 0 and out["attempted"] > 0
        assert set(out["metrics"]) == {"setup_s", "out_tok_s"}


def test_the_cell_and_its_metrics_resolve():
    cell = spec.load_cell(REPO, CELL)
    assert cell.chips == 1 and cell.traffic["clients"] == 32 == cell.traffic["block"]
    assert cell.config["serving"]["slots"] == 32
    assert [m.name for m in cell.reported(trace=False)] == ["setup_s", "out_tok_s"]
    assert {m.name for m in cell.reported(trace=True)} == {
        "flash_roofline.mla", "mfu.mla", "mixed_step_ms.mla", "engine_host_ms.mla",
        "idle_share.mla", "latent_attn_ms.mla", "launch_ms.mla", "wait_idle_ms.mla"}
    kernels = cell.kernels()
    assert kernels["latent"] == "flash_attention_paged_latent"
    assert devtrace.re.search(kernels["flash"], "flash_attention_paged_latent f32[32]")


def test_latent_attn_ms_reads_the_latent_kernel_per_forward():
    reader = spec._load_reader(REPO / "bench", "latent_attn_ms.mla")
    win = loop.Window(seconds=1.0, end=1.0, trace_steps=range(3, 7))
    tr = devtrace.Trace(spans=[(devtrace.STEP_SPAN, 0.0, 1.0)])
    tr.devices["/device:TPU:0"] = [
        devtrace.Op("k", 0.1, 0.3, "flash_attention_paged_latent bf16[32,1024,512]"),
        devtrace.Op("k", 0.4, 0.5, "flash_attention_paged_latent bf16[32,16,512]"),
        devtrace.Op("g", 0.6, 0.9, "flash_attention_paged bf16[96,64,128]"),
    ]
    r = spec.Run(cell=None, window=win, setup_s=0.0, peak=PEAK, chips=1,
                 trace=devtrace.summarize(tr, dict(reader.KERNELS, flash="flash_attention")))
    assert reader.reduce(r) == pytest.approx(1e3 * 0.3 / 4)
    assert reader.reduce(spec.Run(cell=None, window=win, setup_s=0.0, peak=PEAK,
                                  chips=1)) is None
    gqa = devtrace.summarize(tr, {"flash": "flash_attention"})
    assert reader.reduce(spec.Run(cell=None, window=win, setup_s=0.0, peak=PEAK,
                                  chips=1, trace=gqa)) is None


@pytest.mark.parametrize("key,value", [
    ("n_group", 4), ("topk_group", 2), ("q_lora_rank", 1536), ("scoring_func", "softmax")])
def test_the_program_refuses_what_it_does_not_serve(key, value):
    """Group-limited routing, a query low-rank and softmax scoring are
    refused by name, not served as something else."""
    with pytest.raises(ValueError, match=f"the file has .*{key}"):
        model.program_config(dict(TINY, **{key: value}))
