#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
its configuration, traffic mix and metric readers are files under
``bench/`` found by name (``bench/spec.py``), and so is everything that
depends on the architecture: the family module that the configuration's
``model_type`` names (``bench/families/<model_type>.py``).  The run:

1. stops with exit code 2 and no result unless JAX's first device is a TPU
   listed in ``bench/peaks.json`` and there are as many as the cell asks for;
2. set-up: plans the placement over the cell's chips, makes the weights on
   each layer's chip from ``--seed``, builds ``ServingEngine`` with that plan,
   and compiles the two step shapes (``[slots, chunk]`` and ``[slots, 1]``)
   or reads them from ``bench/.jax_cache``;
3. measures for ``--seconds``: the traffic mix's requests go through
   ``submit``/``step``; with ``--trace 1`` the profiler records the last
   seconds of the window and the per-layer metrics are reported;
4. reads the peak device memory, frees the engine, and checks the served
   tokens of a seeded sample of finished requests against the float32
   reference (``bench/reference.py``).

Diagnostics go to stdout as ``[bench]`` lines; the numbers compared, each
with its limit, are the last lines on stderr; the last line on stdout is the
result object.
"""

import time

T_START = time.perf_counter()   # the set-up clock starts before JAX is imported

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".work" / "trace"
TRACE_SECONDS = 4.0      # the traced part of a --trace 1 window
SAMPLE = 4               # finished requests checked against the reference

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if sys.path[0] == str(Path(__file__).resolve().parent):
    sys.path.pop(0)      # bench/ itself is not a package root
sys.path.insert(1, str(ROOT / "src"))

from bench import loop, model, spec, traffic  # noqa: E402


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Backend compiles (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def chips_for(cell: spec.Cell, peaks: dict):
    """The cell's devices; raises NoChip off a listed TPU or short of chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if devs[0].device_kind not in peaks["chips"]:
        raise NoChip(f"no published peaks for {devs[0].device_kind!r} in bench/peaks.json")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX finds {len(devs)}")
    return devs[: cell.chips], peaks["chips"][devs[0].device_kind]


def build(cell: spec.Cell, seed: int, devices, marks: dict):
    """Plan, weights, engine: the system under test, set up as a deployment
    would.  ``marks`` collects the set-up split."""
    import jax

    from repro.core.devices import device_cluster
    from repro.core.modelgraph import transformer_graph
    from repro.core.placement import PlanConfig, plan
    from repro.serving.engine import ServingEngine
    from repro.serving.stage_executor import stages_from_placement

    conf, sv = cell.config, cell.config["serving"]
    cfg = model.program_config(conf)
    pc = PlanConfig(**sv["plan"], serving_slots=sv["slots"],
                    prefill_chunk=sv["prefill_chunk"])
    graph = transformer_graph(cfg, seq_len=sv["max_len"], granularity="block")
    cluster = device_cluster(devices)
    placed = plan(graph, cluster, pc)
    stages = stages_from_placement(graph, placed.placement, devices, cfg.n_layers)
    marks["plan"] = time.perf_counter()
    home = {}
    for st in stages:
        for i in st.layer_ids:
            home[i] = st.device
    first, last = stages[0].device, stages[-1].device

    def device_of(path):
        top = path[0].key
        if top == "layers":
            return home[path[1].idx]
        return first if top == "embed" else last

    held = model.experts_held(conf, cfg)
    params = model.make_params(conf, held, seed, device_of)
    jax.block_until_ready(params)
    marks["params"] = time.perf_counter()
    eng = ServingEngine(
        cfg, params, cluster, devices=list(devices), slots=sv["slots"],
        max_len=sv["max_len"], plan_cfg=pc, eos_id=-1, placement_result=placed,
    )
    marks["engine"] = time.perf_counter()
    log(f"{cell.name}: {model.describe(conf, held)}; placement "
        f"{placed.method} ({placed.status}), {len(stages)} stage(s): "
        + ", ".join(f"{st.device} layers {st.layer_ids[:1]}..{st.layer_ids[-1:]}"
                    f"{' +embed' if st.first else ''}{' +head' if st.last else ''}"
                    for st in stages))
    return eng, params


def warm_up(eng, sv: dict):
    """Compile (or read from the cache) every program a step runs, at the
    two shapes the window uses, and the page copy that admission makes when
    a prompt starts inside a registered page; all of it writes only the
    trash page."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    eng._ensure_caches()
    slots = sv["slots"]
    for width in (sv["prefill_chunk"], 1):
        logits, _ = eng.executor.forward(
            jnp.zeros((slots, width), jnp.int32), eng.caches,
            cache_pos=np.zeros(slots, np.int32), kind="fused",
            q_lens=jnp.asarray(np.zeros(slots, np.int32)),
            page_table=eng._kv_pool.table_array(),
        )
        jax.block_until_ready(jnp.argmax(logits, axis=-1))
        del logits
    trash = eng._kv_pool.num_pages
    jax.block_until_ready(eng.executor.copy_pages(eng.caches, [(trash, trash)]))


def sample(win: loop.Window, seed: int, n: int):
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    done = [r for r in win.reqs if r.done and not r.shed]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + r.out_len, r.rid))
    rest = done[:-1]
    rng = traffic.rng_for(seed, 2)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[-1]] + [rest[i] for i in sorted(pick)]


def verdict(gaps, short: int, limit: float):
    """The numbers compared, each beside its limit, and whether all hold."""
    gap = max(gaps) if gaps and all(map(math.isfinite, gaps)) else math.inf
    numbers = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "short_requests": {"value": short, "limit": 0},
        "tokens_compared": {"value": len(gaps), "limit": 1},
    }
    return gap <= limit and short == 0 and len(gaps) >= 1, numbers


def check(cell: spec.Cell, params, win: loop.Window, seed: int, control=False):
    """Served tokens of a sample against the reference: the widest gap by
    which a served token's reference logit lies below the reference's best,
    and the requests that finished short of their length.  With
    ``control``, also the verdict on the tokens the float8 control ranks
    first at the same positions, judged as if they had been served."""
    from bench import reference

    conf, sv = cell.config, cell.config["serving"]
    picked = sample(win, seed, SAMPLE)
    seqs = [(r.prompt, list(r.engine_req.out_tokens)) for r in picked]
    short = sum(1 for r in win.reqs if r.done and not r.shed
                and len(r.engine_req.out_tokens) != r.out_len)
    g = reference.gaps(params, conf, seqs,
                       pad_to=model.ceil_to(sv["max_len"], reference.Q_BLOCK),
                       out_max=cell.traffic["output"]["max"], control=control)
    limit = conf["correct"]["max_logit_gap"]
    ok, numbers = verdict(g["served"], short, limit)
    ctl = verdict(g["control"], short, limit) if control else None
    return ok, numbers, g, ctl


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, *, t_start: float, fault=None, control=False) -> dict:
    """One run of ``cell``: the result object, plus the reference's
    ``readings`` and the ``window``; with ``control``, the float8 control's
    readings too and its verdict (``control``: ``correct`` and ``check``).
    ``fault(eng)`` may break the timed path for a test."""
    import jax

    from repro.serving.engine import Request

    clock = CompileClock()
    marks = {"jax": time.perf_counter()}
    eng, params = build(cell, seed, devices, marks)
    if fault is not None:
        fault(eng)
    sv, mix = cell.config["serving"], cell.traffic
    vocab = cell.config["vocab_size"]

    def make_request(rid, prompt, max_new):
        return Request(rid=rid, prompt=list(prompt), max_new_tokens=max_new)

    def span(name, i):
        if name == "bench.step":
            return jax.profiler.StepTraceAnnotation(name, step_num=i)
        return jax.profiler.TraceAnnotation(name)

    drv = loop.Runner(eng, make_request, span=span)
    warm_up(eng, sv)
    clients = None
    if mix["loop"] == "closed":
        pool = traffic.closed_pool(mix, seed, vocab)
        clients = [pool() for _ in range(mix["clients"])]
        for r in clients:
            drv.submit(r, 0.0)
        drv.step(loop.Window(seconds=0.0))     # the window opens full
    gc.collect()
    gc.freeze()       # set-up's objects are never garbage: no pass rescans them
    marks["warm"] = time.perf_counter()
    compiles0, hits0 = clock.compiles, clock.hits

    tr = None
    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        tr = (min(TRACE_SECONDS, seconds),
              lambda: jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts),
              jax.profiler.stop_trace)
    if clients is None:
        win = drv.run_open(traffic.open_schedule(mix, seed, seconds, vocab),
                           seconds, trace=tr)
    else:
        win = drv.run_closed(clients, pool, seconds, trace=tr)
    in_window = clock.compiles - compiles0
    setup_s = drv.t_start - t_start       # to the window's opening

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    peak_bytes = max(peaks)
    summary = None
    if trace:
        from bench import devtrace

        summary = devtrace.reduce_dir(str(TRACE_DIR), cell.kernels())
    run = spec.Run(cell=cell, window=win, setup_s=setup_s, peak=peak,
                   chips=len(devices), trace=summary)
    metrics = spec.metric_values(run, trace)

    due = win.due_in_window()
    late = win.lateness or [0.0]
    t = marks
    log(f"set-up {setup_s:.2f} s: process+JAX {t['jax'] - t_start:.2f}, plan "
        f"{t['plan'] - t['jax']:.2f}, params {t['params'] - t['plan']:.2f}, "
        f"engine {t['engine'] - t['params']:.2f}, compile+warm-up "
        f"{t['warm'] - t['engine']:.2f} (compile {clock.seconds:.2f} s over "
        f"{compiles0} programs, persistent-cache hits {hits0}), pre-roll "
        f"{drv.t_start - t['warm']:.2f}")
    log(f"window {win.end:.3f} s (asked {seconds}): {len(win.steps)} steps, "
        f"{sum(1 for s in win.steps if s.width > 1)} with a prefill chunk; "
        f"compiles in the pre-roll and window {in_window}; generator lateness mean "
        f"{1e3 * sum(late) / len(late):.2f} ms max {1e3 * max(late):.2f} ms")
    log(f"requests attempted {len(due)}, completed "
        f"{sum(1 for r in due if r.done and not r.shed)}, failed "
        f"{sum(1 for r in due if r.shed)}; output tokens {win.tokens_in_window()}")
    longest = sorted(win.steps, key=lambda s: s.t1 - s.t0, reverse=True)[:5]
    between = max((b.t0 - a.t1 for a, b in zip(win.steps, win.steps[1:])), default=0.0)
    typical = {w: loop.percentile([s.t1 - s.t0 for s in win.steps if s.width == w], 50)
               for w in {s.width for s in win.steps}}
    held_up = sum(1 for s in win.steps if s.t1 - s.t0 > typical[s.width] + 0.015)
    log("longest steps (start s, wall ms, forward ms, width): " + ", ".join(
        f"({s.t0:.2f}, {1e3 * (s.t1 - s.t0):.1f}, {1e3 * s.fwd_s:.1f}, {s.width})"
        for s in longest) + f"; longest time between steps {1e3 * between:.1f} ms; "
        f"steps over their width's median by 15 ms or more: {held_up}")
    held_open, held_mean = win.slots_held()
    log(f"slots held: {held_open} at the opening, {held_mean:.2f} on average "
        f"over the window, of {sv['slots']}")
    log(f"peak device memory {peak_bytes / 2**30:.3f} GiB" + (
        "; by chip " + ", ".join(f"{p / 2**30:.3f}" for p in peaks)
        if len(peaks) > 1 else ""))
    if summary is not None:
        log(f"trace: window {summary.window_s:.3f} s over {len(win.trace_steps)} "
            f"steps, busy {summary.busy_s:.3f} s (by chip "
            + ", ".join(f"{v:.3f}" for v in summary.busy_by_device.values())
            + f"), kernels {summary.kernel_s} "
            f"calls {summary.kernel_calls}")
    for k, v in metrics.items():
        log(f"{k} = {v['value']:.6g} {v['unit']}")

    del drv
    eng.caches = None
    eng.executor = None
    del eng
    gc.unfreeze()
    gc.collect()
    ok, numbers, readings, ctl = check(cell, params, win, seed, control=control)
    dev = devices[0]
    out = {
        "correct": bool(ok),
        "attempted": len(due),
        "failed": sum(1 for r in due if r.shed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes)},
    }
    if summary is not None:
        out["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["check"] = numbers
    out["readings"], out["window"] = readings, win
    if ctl is not None:
        out["control"] = {"correct": bool(ctl[0]), "check": ctl[1]}
    return out


def print_result(out: dict):
    for k, v in out["check"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr,
              flush=True)
    keys = ("correct", "attempted", "failed", "metrics", "device", "breakdown", "check")
    print(json.dumps({k: out[k] for k in keys if k in out}), flush=True)


def setup_jax():
    """JAX's persistent compilation cache at the checkout's fixed path; the
    program reads the same variable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # never evict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    cell = spec.load_cell(ROOT, args.workload)
    with open(ROOT / "bench" / "peaks.json") as f:
        peaks = json.load(f)
    setup_jax()
    try:
        devices, peak = chips_for(cell, peaks)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   peak, t_start=T_START)
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
