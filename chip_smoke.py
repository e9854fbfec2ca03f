#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, at full llama3.2-1b width.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --chips 4      # cross-chip paths only (4 chips)

One process, run from the root of the checkout.  It stops with a non-zero
exit before serving unless JAX's first device is a TPU.  The model is
llama3.2-1b at its published widths in bf16, with random params made from
``--seed``; nothing is downloaded.  Requests go through the normal path:
planner → ``ServingEngine`` → ``StageExecutor`` → Pallas flash kernels.

One chip: serve 8 requests (prompts of 16–600 tokens, 32 new tokens each;
8 slots, ``max_len`` 2048, fused 64-token chunked prefill) with dense KV,
again warm, and with paged KV (64-token pages).  Checks: every request gets
all its tokens; the warm run repeats the first token for token; the lowered
stage program holds ``tpu_custom_call`` (the kernel, not interpret mode);
the logits of the engine's first step agree with a plain f32 reference
(naive attention, f32 params, ``default_matmul_precision("highest")``);
and paged KV computes the same model as dense KV.

``--chips 4``: the one-chip engine against a 4-stage pipeline (one stage
per chip, forced through ``placement_result``) and against four one-chip
replicas behind ``Router``, all on the same requests.

"The same model" has two tests.  Where both sides run the same compiled
program (the warm run, the router's one-chip replicas) their tokens must be
equal.  Where the programs differ (dense vs paged KV, one stage vs four)
XLA may round in other places, and with random weights in bf16 the top two
logits are often within that rounding, so free-running greedy tokens can
part.  There the second engine is fed the first one's tokens (teacher
forcing) and every emitted token's logits must agree within
``SAME_MODEL_TOL``; the greedy tokens it would have chosen are counted.

Each check fails loudly.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# Reference tolerance on the first step's logits, over every valid
# position: ||served - ref||_2 / ||ref||_2.  The served model keeps bf16
# params and bf16 activations between ops (8-bit significand: one rounding
# is a relative error of up to 2^-9 ≈ 0.002) through 16 residual blocks,
# while the reference runs the same params in f32 end to end.  A CPU run of
# this check with 16 layers at reduced widths measured 1.4e-2 to 1.5e-2;
# 5e-2 leaves room for the chip's own rounding and still fails a wrong mask,
# position or cache layout, which give errors of order 1.
REF_REL_L2_TOL = 5e-2

# Two programs of the same bf16 model, fed the same tokens: relative L2
# between their logits for each emitted token.  They differ only in where
# XLA rounds, which cannot exceed the bf16-vs-f32 error bounded above; a
# wrong page, mask or stage hand-off gives errors of order 1.
SAME_MODEL_TOL = 5e-2

ARCH = "llama3.2-1b"
SLOTS, MAX_LEN, CHUNK, PAGE, NEW_TOKENS, N_REQUESTS = 8, 2048, 64, 64, 32, 8


def fail(msg: str):
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def make_prompts(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = np.linspace(16, 600, N_REQUESTS).astype(int)
    return [[int(t) for t in rng.integers(1, vocab, size=n)] for n in lens]


def requests(prompts):
    from repro.serving.engine import Request

    return [
        Request(rid=i, prompt=list(p), max_new_tokens=NEW_TOKENS)
        for i, p in enumerate(prompts)
    ]


def engine(cfg, params, devices, *, page_tokens=None, placement_result=None):
    from repro.core.devices import device_cluster
    from repro.core.placement import PlanConfig
    from repro.serving.engine import ServingEngine

    plan_cfg = PlanConfig(
        method="moirai", time_limit=20, objective="throughput",
        serving_slots=SLOTS, prefill_chunk=CHUNK, fused_prefill=True,
        kv_page_tokens=page_tokens,
    )
    return ServingEngine(
        cfg, params, device_cluster(devices), devices=list(devices),
        slots=SLOTS, max_len=MAX_LEN, plan_cfg=plan_cfg, eos_id=-1,
        placement_result=placement_result,
    )


def check_served(name, reqs):
    from repro.launch.serve import unserved

    bad = unserved(reqs)
    if bad:
        fail(f"{name}: {len(bad)} request(s) unserved: "
             f"{[(r.rid, r.state, len(r.out_tokens)) for r in bad]}")


def check_same(name, reqs, ref) -> bool:
    """Tokens equal to the one-chip engine's (both sides run the same
    program); a mismatch is reported here and fails the run at its end,
    after every comparison has run."""
    same = True
    for r, q in zip(reqs, ref):
        if r.out_tokens != q.out_tokens:
            at = next(
                i for i, (a, b) in enumerate(zip(r.out_tokens, q.out_tokens))
                if a != b
            )
            log(f"MISMATCH {name}: request {r.rid} (prompt {len(r.prompt)}) "
                f"differs from the one-chip engine at token {at}: "
                f"{r.out_tokens[at:at + 4]} vs {q.out_tokens[at:at + 4]}")
            same = False
    if same:
        log(f"{name}: tokens identical to the one-chip engine "
            f"({len(reqs)} requests)")
    return same


def check_same_model(name, run, ref) -> bool:
    """``run`` was fed ``ref``'s tokens: the logits behind every emitted
    token must agree within SAME_MODEL_TOL; greedy choices that part are
    counted, with the reference's margin between the two tokens."""
    errs, flips = [], []
    for rid, rows in ref.logits.items():
        for k, (a, b) in enumerate(zip(rows, run.logits[rid])):
            errs.append(float(np.linalg.norm(b - a) / np.linalg.norm(a)))
            own = run.chosen[rid][k]
            if own != ref.chosen[rid][k]:
                flips.append(float(a.max() - a[own]))
    worst = max(errs)
    log(f"{name} (fed the one-chip tokens): logits rel L2 per token max "
        f"{worst:.3e} median {float(np.median(errs)):.3e} (tol "
        f"{SAME_MODEL_TOL:.0e}) over {len(errs)} tokens; its own greedy "
        f"choice differs at {len(flips)} token(s)"
        + (f", reference margin there at most {max(flips):.3e}"
           if flips else ""))
    if not worst <= SAME_MODEL_TOL:
        log(f"MISMATCH {name}: logits off the one-chip engine by {worst:.3e}")
        return False
    return True


class Run:
    """One engine run: per request, the f32 logits row behind each emitted
    token and the token the engine itself chose there."""

    def __init__(self, reqs):
        self.reqs = reqs
        self.logits = {r.rid: [] for r in reqs}
        self.chosen = {r.rid: [] for r in reqs}

    def tokens(self):
        return {r.rid: list(r.out_tokens) for r in self.reqs}


def serve(name, eng, reqs, *, force=None) -> Run:
    """Serve ``reqs`` to the end.  ``force`` (rid → tokens) feeds the engine
    those tokens in place of its own greedy choices (teacher forcing)."""
    from repro.serving.tracing import compile_stats

    run, last = Run(reqs), {}
    forward = eng.executor.forward

    def tapped(*args, **kw):
        last["active"] = list(eng.active)
        logits, caches = forward(*args, **kw)
        last["logits"], last["q_lens"] = logits, np.asarray(kw["q_lens"])
        return logits, caches

    eng.executor.forward = tapped
    c0, t0 = compile_stats()[1], time.perf_counter()
    for r in reqs:
        eng.submit(r)
    steps = 0
    while True:
        counts = [len(r.out_tokens) for r in reqs]
        n = eng.step()
        steps += 1
        for r, c in zip(reqs, counts):
            if len(r.out_tokens) == c:
                continue
            i = next(i for i, a in enumerate(last["active"]) if a is r)
            row = last["logits"][i, int(last["q_lens"][i]) - 1]
            run.logits[r.rid].append(np.asarray(row, np.float32))
            run.chosen[r.rid].append(r.out_tokens[-1])
            if force is not None:
                r.out_tokens[-1] = force[r.rid][len(r.out_tokens) - 1]
        if n == 0 and not eng.queue:
            break
        if steps > 10_000:
            fail(f"{name}: not drained after {steps} steps")
    wall = time.perf_counter() - t0
    eng.executor.forward = forward
    toks = sum(len(r.out_tokens) for r in reqs)
    log(f"{name}: {steps} steps, {toks} tokens, {wall:.2f} s wall, "
        f"compile {compile_stats()[1] - c0:.2f} s, "
        f"{toks / wall:.1f} tok/s (informational, host clock)")
    check_served(name, reqs)
    return run


def first_step_inputs(prompts):
    """The engine's first fused step: every slot's first prompt chunk at
    depth 0."""
    tokens = np.zeros((SLOTS, CHUNK), np.int32)
    q_lens = np.zeros(SLOTS, np.int32)
    for i, p in enumerate(prompts[:SLOTS]):
        n = min(CHUNK, len(p))
        tokens[i, :n] = p[:n]
        q_lens[i] = n
    return tokens, q_lens


def check_kernel_on_path(eng, prompts):
    tokens, q_lens = first_step_inputs(prompts)
    lowered = eng.executor.trace_stage(
        0, jnp.asarray(tokens), eng.executor.init_caches(SLOTS, MAX_LEN),
        cache_pos=jnp.zeros(SLOTS, jnp.int32), q_lens=jnp.asarray(q_lens),
    ).lower()
    n = lowered.as_text().count("tpu_custom_call")
    if n == 0:
        fail("the stage program holds no tpu_custom_call: the kernel is "
             "not on the path")
    log(f"stage program: {n} tpu_custom_call site(s)")


def check_reference(cfg, params, eng, prompts):
    """First-step logits of the served engine against a plain f32 model."""
    import dataclasses

    from repro.models import transformer

    tokens, q_lens = first_step_inputs(prompts)
    ex = eng.executor
    logits, _ = ex.forward(
        jnp.asarray(tokens), ex.init_caches(SLOTS, MAX_LEN),
        cache_pos=np.zeros(SLOTS, np.int32), kind="fused",
        q_lens=jnp.asarray(q_lens),
    )
    got = np.asarray(logits, np.float32)
    del logits
    ref_cfg = dataclasses.replace(cfg, dtype="float32", attention_impl="naive")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda p, t: transformer.train_forward(p, {"tokens": t}, ref_cfg)[0]
        )(p32, jnp.asarray(tokens))
    ref = np.asarray(ref, np.float32)
    del p32
    valid = np.arange(CHUNK)[None, :] < q_lens[:, None]
    g, r = got[valid], ref[valid]
    rel = float(np.linalg.norm(g - r) / np.linalg.norm(r))
    top1 = float(np.mean(g.argmax(-1) == r.argmax(-1)))
    log(f"reference: rel L2 {rel:.3e} (tol {REF_REL_L2_TOL:.0e}), max abs "
        f"{float(np.abs(g - r).max()):.3e} vs max |ref| "
        f"{float(np.abs(r).max()):.3e}, top-1 agreement {top1:.3f} over "
        f"{int(valid.sum())} positions")
    if not np.isfinite(g).all():
        fail("non-finite logits")
    if not rel <= REF_REL_L2_TOL:
        fail(f"first-step logits off the f32 reference: rel L2 {rel:.3e}")


def one_chip(cfg, params, prompts, devs):
    dev = devs[:1]
    dense = engine(cfg, params, dev)
    log(f"dense engine: placement={dense.placement_result.method}, "
        f"stages={len(dense.executor.stages)}")
    ref = serve("dense", dense, requests(prompts))
    warm = serve("dense warm", dense, requests(prompts))
    same = check_same("dense warm", warm.reqs, ref.reqs)
    check_kernel_on_path(dense, prompts)
    check_reference(cfg, params, dense, prompts)
    del dense
    paged = engine(cfg, params, dev, page_tokens=PAGE)
    run = serve("paged", paged, requests(prompts), force=ref.tokens())
    log(f"paged kv pool: {paged._kv_pool.stats()}")
    return check_same_model("paged", run, ref) and same


def four_chips(cfg, params, prompts, devs):
    from repro.core.milp import PlacementResult
    from repro.core.modelgraph import transformer_graph
    from repro.serving.router import Replica, Router, RouterConfig
    from repro.serving.tracing import compile_stats

    if len(devs) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(devs)}")
    ref = serve("one chip", engine(cfg, params, devs[:1]), requests(prompts))

    # 4-stage pipeline: layer-graph chain cut into 4 contiguous stages
    graph = transformer_graph(cfg, seq_len=MAX_LEN, granularity="block")
    order = graph.topo_order()
    forced = PlacementResult(
        placement={nid: i * 4 // len(order) for i, nid in enumerate(order)},
        objective=0.0, status="forced", mip_gap=0.0, solve_time=0.0,
        method="forced-4-stage",
    )
    pipe = engine(cfg, params, devs, placement_result=forced)
    homes = [
        {d for leaf in jax.tree.leaves(sp) for d in leaf.devices()}
        for sp in pipe.executor.stage_params
    ]
    if len(homes) != 4 or any(len(h) != 1 for h in homes) or len(
        set().union(*homes)
    ) != 4:
        fail(f"pipeline stage params are not on 4 distinct devices: {homes}")
    log("pipeline: 4 stages, params on "
        f"{[str(next(iter(h))) for h in homes]}, layers "
        f"{[st.layer_ids for st in pipe.executor.stages]}")
    run = serve("4-stage pipeline", pipe, requests(prompts),
                force=ref.tokens())
    same = check_same_model("4-stage pipeline", run, ref)
    del pipe

    reps = [
        Replica(name=f"replica{i}", devices=[i],
                engine=engine(cfg, params, [d]))
        for i, d in enumerate(devs)
    ]
    router = Router(reps, config=RouterConfig(tiers=1))
    reqs = requests(prompts)
    c0, t0 = compile_stats()[1], time.perf_counter()
    for r in reqs:
        router.submit(r)
    router.run_until_drained()
    wall = time.perf_counter() - t0
    stats = router.stats()
    per_rep = {
        r["name"]: sum(
            1 for e in router.events
            if e["kind"] == "dispatch" and e.get("replica") == r["name"]
        )
        for r in stats["replicas"]
    }
    log(f"router: {len(reps)} replicas, dispatches {per_rep}, "
        f"{wall:.2f} s wall, compile {compile_stats()[1] - c0:.2f} s, "
        f"counters {stats['counters']}")
    if stats["counters"]["crashed_replicas"]:
        fail("a replica crashed")
    if sum(1 for n in per_rep.values() if n) < 2:
        fail(f"the router used fewer than 2 replicas: {per_rep}")
    check_served("router", reqs)
    return check_same("4 router replicas", reqs, ref.reqs) and same


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models.model import build_model, param_count
    from repro.serving.tracing import compile_stats   # counts from here

    log(f"devices: {len(devs)}x {devs[0].device_kind}; compile cache "
        f"{setup_compile_cache()}")
    cfg = get_config(ARCH).serving()
    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"{cfg.name}: {param_count(params) / 1e9:.3f} B params "
        f"({cfg.dtype}), d_model={cfg.d_model} layers={cfg.n_layers} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
        f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}, "
        f"attention={cfg.attention_impl}, seed {args.seed}, built in "
        f"{time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(args.seed, cfg.vocab_size)
    log(f"{len(prompts)} requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens each; "
        f"{SLOTS} slots, max_len {MAX_LEN}, chunk {CHUNK}")

    phase = four_chips if args.chips == 4 else one_chip
    same = phase(cfg, params, prompts, devs)

    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"{d}: peak memory "
            f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}")
    n, sec = compile_stats()
    log(f"compile total {sec:.2f} s over {n} compiles")
    if not same:
        fail("the engines disagree (MISMATCH lines above)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
