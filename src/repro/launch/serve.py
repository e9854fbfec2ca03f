"""Serving launcher CLI: Moirai placement → stage executor → batch engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 8 --method moirai

Without ``--smoke`` the model is served at its published widths (on a
TPU).  The serving variant of the config (``ModelConfig.serving``) holds
per-layer params and attends with the Pallas kernel (interpret mode off the
TPU).  The planner sees the chips ``jax.devices()`` reports, with the peaks
of their ``device_kind`` (``core.devices.CHIP_PEAKS``).

The engine runs with the adaptive observe → derate → replan loop closed
(an observation window every ``--adapt-every`` decode steps; ``0`` disables
it).  After the run the CLI prints the straggler report, every adaptation
decision the policy logged, and every committed replan (hot-swap) with its
derate map — the operator-facing view of the loop.

``--replicas auto|N`` switches to the multi-replica service: the replica
planner (:func:`repro.core.replica.plan_replicas`) jointly picks the
replica count and per-replica device subsets, and an SLO-aware router
(:class:`repro.serving.router.Router`) dispatches requests across the
per-replica engines.  ``--replicas 1`` (the default) is the single-engine
path above, verbatim.  With replicas the CLI additionally prints the
service plan, the router's event log (submits, dispatches, drains, replica
spawns) and the per-tier latency report.

In a run with no fault schedule, a crashed replica or a request that ends
without all its tokens makes the CLI exit non-zero.

``--fault-schedule PATH`` replays a chaos scenario
(:class:`repro.serving.faults.FaultSchedule` JSON — write one with
``FaultSchedule([...]).save(path)`` or ``FaultSchedule.random(...)``)
into the run: scheduled device crashes, stalls, and link degradations
land at their scripted steps, against the single engine or routed across
replicas, and the fault log is printed after the run.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.core.devices import device_cluster
from repro.core.placement import PlanConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.models.model import build_model
from repro.serving.adaptation import AdaptationConfig
from repro.serving.engine import Request, ServingEngine


def unserved(reqs):
    """Requests that did not finish with every token they asked for."""
    return [
        r for r in reqs
        if r.state != "finished" or len(r.out_tokens) != r.max_new_tokens
    ]


def _check_served(args, reqs, crashed: int = 0):
    """Without a fault schedule every request must be served and no replica
    may crash: exit non-zero otherwise."""
    if args.fault_schedule:
        return
    bad = unserved(reqs)
    if bad or crashed:
        raise SystemExit(
            f"[serve] FAILED: {len(bad)} request(s) unserved "
            f"({[(r.rid, r.state, len(r.out_tokens)) for r in bad[:8]]}), "
            f"{crashed} replica crash(es)"
        )


def _serve_replicas(args, cfg, params, cluster, plan_cfg):
    """The --replicas path: plan the service, run the router, print the
    operator view (service plan, event log, per-tier latencies)."""
    import dataclasses

    from repro.core.modelgraph import transformer_graph
    from repro.core.replica import plan_replicas
    from repro.serving.router import Router, RouterConfig

    # the replica planner must score the SAME graph the engines execute
    graph = transformer_graph(cfg, seq_len=args.max_len, granularity="block")
    plan_cfg = dataclasses.replace(
        plan_cfg,
        replicas="auto" if args.replicas == "auto" else int(args.replicas),
        slo_p99=args.slo_p99,
    )
    t0 = time.perf_counter()
    svc = plan_replicas(graph, cluster, plan_cfg)
    t_plan = time.perf_counter() - t0
    print(
        f"[serve] service plan ({t_plan:.1f}s): {svc.n_replicas} replica(s) "
        f"on {cluster.name}, total {svc.total_rps:.1f} req/s steady, "
        f"p99 {svc.p99_s*1e3:.1f} ms @ {svc.extra['offered_rps']:.1f} req/s "
        f"offered, slo_ok={svc.slo_ok}"
    )
    for i, spec in enumerate(svc.replicas):
        print(
            f"[serve]   replica{i}: devices={spec.devices} "
            f"bneck={spec.bottleneck_s*1e3:.2f} ms "
            f"({spec.throughput_rps:.1f} req/s)"
        )
    router = Router.from_service_plan(
        cfg, params, cluster, svc,
        slots=args.slots, max_len=args.max_len, plan_cfg=plan_cfg,
        config=RouterConfig(dispatch=args.dispatch),
        eos_id=-1,
        admission=args.admission, oversize=args.oversize,
    )
    injector = None
    if args.fault_schedule:
        from repro.serving.faults import FaultInjector, FaultSchedule

        schedule = FaultSchedule.load(args.fault_schedule)
        injector = FaultInjector(schedule)
        router.attach_fault_injector(injector)
        print(
            f"[serve] chaos: replaying '{schedule.name}' "
            f"({len(schedule)} events, horizon {schedule.horizon} steps)"
        )
    t0 = time.perf_counter()
    reqs = [
        Request(rid=i, prompt=[1 + i % 7, 2, 3, 4],
                max_new_tokens=args.max_new_tokens)
        for i in range(args.requests)
    ]
    n_tiers = router.config.tiers
    for i, r in enumerate(reqs):
        router.submit(r, tier=i % n_tiers)   # spread load over the tiers
    router.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    print(f"[serve] {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s) "
          f"across {sum(r.state != 'retired' for r in router.replicas)} "
          "live replica(s)")
    for t, row in router.latency_report().items():
        print(
            f"[serve]   tier {t}: {int(row['count'])} done, "
            f"mean {row['mean_steps']:.1f} / max {int(row['max_steps'])} "
            "router steps"
        )
    stats = router.stats()
    print(f"[router] counters: {stats['counters']} slo_ok={stats['slo_ok']}")
    print(f"[router] terminal states: {stats['finished_by_state']}")
    print(f"[router] {len(router.events)} events")
    for ev in router.events:
        detail = " ".join(
            f"{k_}={v_}" for k_, v_ in ev.items()
            if k_ not in ("step", "kind")
        )
        print(f"[router]   s{ev['step']:<4d} {ev['kind']:<14s} {detail}")
    if injector is not None:
        print(f"[chaos] {len(injector.log)} injections")
        for entry in injector.log:
            e = entry["event"]
            tgt = e["device"] if e["device"] is not None else tuple(e["link"])
            print(
                f"[chaos]   s{entry['clock']:<4d} {e['kind']:<14s} "
                f"target={tgt} -> {entry['status']}"
            )
    _check_served(args, reqs, crashed=stats["counters"]["crashed_replicas"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--method", default="moirai")
    ap.add_argument(
        "--heterogeneous", action="store_true",
        help="plan as if every odd chip ran at half speed (a mixed-"
        "generation fleet); off by default",
    )
    ap.add_argument(
        "--adapt-every", type=int, default=16,
        help="decode steps per adaptation observation window (0 = off; "
        "short windows lower the per-window evidence requirement to match)",
    )
    ap.add_argument(
        "--admission", choices=("queue", "reject"), default="queue",
        help="KV-aware admission: hold requests in queue or reject them",
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=64, metavar="TOKENS",
        help="stream prompts in chunks of this many tokens, packed into the "
        "decode batch's one fused forward per step; also the chunk size the "
        "planner's prefill-aware throughput scoring assumes",
    )
    ap.add_argument(
        "--kv-page-tokens", type=int, default=0, metavar="TOKENS",
        help="serve the KV cache as fixed-size pages of this many tokens "
        "(block-paged attention): slots allocate pages on demand instead of "
        "dense max-len rows, and the planner's Eq. 5 memory term charges "
        "pages actually resident (0 = dense per-slot rows, the default)",
    )
    ap.add_argument(
        "--no-prefix-sharing", dest="prefix_sharing", action="store_false",
        help="disable hash-based prefix sharing across paged requests "
        "(shared prompt prefixes reuse read-only pages, skip their prefill "
        "chunks, and copy-on-write at first divergence); only meaningful "
        "with --kv-page-tokens",
    )
    ap.add_argument(
        "--kv-residency", type=float, default=1.0, metavar="FRACTION",
        help="expected fraction of max-len a sequence actually occupies — "
        "scales the planner's paged Eq. 5 memory term (1.0 = worst case; "
        "only meaningful with --kv-page-tokens)",
    )
    ap.add_argument(
        "--draft", default=None, metavar="ARCH",
        help="serve speculatively: this (smaller) arch drafts --spec-tokens "
        "greedy tokens per ready slot between target steps and ONE fused "
        "target forward verifies them (variable per-slot advance, token-"
        "identical to plain greedy); draft + target are placed JOINTLY over "
        "the merged pass-rate graph (shared Eq. 5 memory, per-device busy "
        "summed across both models) — the draft lands on devices the target "
        "leaves idle.  Single-engine path only (not with --replicas); "
        "dense/moe draft archs only (the stage executor serves attention-"
        "family blocks)",
    )
    ap.add_argument(
        "--spec-tokens", type=int, default=4, metavar="K",
        help="draft tokens proposed per speculative round (with --draft)",
    )
    ap.add_argument(
        "--acceptance-rate", type=float, default=0.75, metavar="A",
        help="the acceptance rate the joint planner assumes when scoring "
        "draft/target placements (expected tokens per round "
        "E = (1-a^(k+1))/(1-a)); compare against the observed per-class "
        "rates in the post-run speculation report",
    )
    ap.add_argument(
        "--prompt-len", type=int, default=0, metavar="TOKENS",
        help="expected prompt tokens per request: lets the throughput "
        "planner charge each request's chunked-prefill work when scoring "
        "placements (0 = decode-only scoring)",
    )
    ap.add_argument(
        "--oversize", choices=("truncate", "reject"), default="truncate",
        help="requests whose prompt+max_new_tokens overflow --max-len are "
        "truncated (oldest prompt tokens dropped, flagged) or rejected",
    )
    ap.add_argument(
        "--derate-state", default=None, metavar="PATH",
        help="persist the adaptive derate policy's state here; a restarted "
        "engine resumes its learned derates instead of re-observing",
    )
    ap.add_argument(
        "--fault-schedule", default=None, metavar="PATH",
        help="replay this chaos scenario (FaultSchedule JSON) into the run: "
        "scheduled device crashes/stalls and link degradations fire at their "
        "scripted engine/router steps (see repro.serving.faults)",
    )
    ap.add_argument(
        "--replicas", default="1", metavar="auto|N",
        help="serve N model replicas behind the SLO-aware router, or 'auto' "
        "to let the replica planner pick the count that maximizes total "
        "steady req/s under --slo-p99 (default 1 = single engine, no router)",
    )
    ap.add_argument(
        "--slo-p99", type=float, default=None, metavar="SECONDS",
        help="p99 request-latency SLO the replica planner's simulation must "
        "meet (only with --replicas; no SLO = pure throughput maximization)",
    )
    ap.add_argument(
        "--dispatch", choices=("least_loaded", "shortest_prefill"),
        default="least_loaded",
        help="router dispatch policy across replicas (only with --replicas)",
    )
    ap.add_argument(
        "--cluster-size", type=int, default=None, metavar="K",
        help="chips in the modeled cluster (default: the devices jax "
        "reports; raise it to plan multi-replica services on a fleet of "
        "such chips bigger than this host)",
    )
    args = ap.parse_args(argv)
    if args.prefill_chunk <= 0:
        ap.error("--prefill-chunk must be a positive int")

    cache_dir = setup_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = cfg.serving()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    devices = jax.devices()
    cluster = device_cluster(
        devices, args.cluster_size, heterogeneous=args.heterogeneous
    )
    print(
        f"[serve] devices: {len(devices)}x {devices[0].device_kind} "
        f"({devices[0].platform}); planning on {cluster.name}; "
        f"compile cache {cache_dir}"
    )
    plan_cfg = PlanConfig(
        method=args.method, time_limit=20, mip_rel_gap=0.05,
        # mirror the engine's own default: serving >1 slot is a
        # pipelined workload, scored by bottleneck-stage time — and
        # prefill-aware scoring (--prompt-len) only exists there
        objective="throughput" if args.slots > 1 else "latency",
        serving_slots=args.slots,
        prefill_chunk=args.prefill_chunk,
        prompt_len=args.prompt_len,
        kv_page_tokens=args.kv_page_tokens or None,
        prefix_sharing=args.prefix_sharing,
        kv_residency=args.kv_residency,
        spec_tokens=args.spec_tokens if args.draft else 0,
        acceptance_rate=args.acceptance_rate,
    )
    if args.replicas != "1":
        if args.draft:
            ap.error("--draft is the single-engine path (not with --replicas)")
        return _serve_replicas(args, cfg, params, cluster, plan_cfg)
    draft_kw = {}
    if args.draft:
        draft_cfg = get_config(args.draft)
        if args.smoke:
            draft_cfg = draft_cfg.smoke()
        draft_cfg = draft_cfg.serving()
        draft_model = build_model(draft_cfg)
        draft_kw = dict(
            draft_cfg=draft_cfg,
            draft_params=draft_model.init(jax.random.PRNGKey(1)),
        )
    engine = ServingEngine(
        cfg, params, cluster,
        slots=args.slots, max_len=args.max_len,
        plan_cfg=plan_cfg,
        eos_id=-1,
        **draft_kw,
        # short windows can't carry the default 4-sample evidence minimum —
        # scale it down so --adapt-every 1..3 still observes (and acts)
        adapt=AdaptationConfig(
            window_steps=args.adapt_every,
            min_samples=(
                min(4, args.adapt_every) if args.adapt_every > 0 else 4
            ),
            state_path=args.derate_state,
        ),
        admission=args.admission,
        oversize=args.oversize,
    )
    injector = None
    if args.fault_schedule:
        from repro.serving.faults import FaultInjector, FaultSchedule

        schedule = FaultSchedule.load(args.fault_schedule)
        injector = FaultInjector(schedule)
        engine.attach_fault_injector(injector)
        print(
            f"[serve] chaos: replaying '{schedule.name}' "
            f"({len(schedule)} events, horizon {schedule.horizon} steps)"
        )
    print(
        f"[serve] {args.arch}: placement={engine.placement_result.method} "
        f"stages={len(engine.executor.stages)} devices={len(engine.devices)} "
        f"adapt_every={args.adapt_every or 'off'} "
        f"prefill_chunk={engine.prefill_chunk}"
        + (
            f" kv=paged({engine.kv_page_tokens}"
            f"{',shared' if engine.prefix_sharing else ''})"
            if engine.kv_page_tokens else " kv=dense"
        )
        + (
            f" spec=draft:{args.draft},k={engine.spec_tokens}"
            if args.draft else ""
        )
    )
    t0 = time.perf_counter()
    reqs = [
        Request(rid=i, prompt=[1 + i % 7, 2, 3, 4], max_new_tokens=args.max_new_tokens)
        for i in range(args.requests)
    ]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    rejected = sum(r.rejected for r in reqs)
    print(f"[serve] {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s)"
          + (f", {rejected} rejected by KV admission" if rejected else ""))
    if engine._kv_pool is not None:
        print(f"[serve] kv pool: {engine._kv_pool.stats()}")
    print(f"[serve] straggler report: {engine.straggler_report()['stragglers']}")
    if args.draft:
        spec = engine.speculation_report()
        print(
            f"[spec] k={spec['spec_tokens']} planned a="
            f"{spec['planned_acceptance_rate']:.2f} "
            f"(E={spec['planned_tokens_per_round']:.2f} tok/round)"
        )
        for cls, row in spec["classes"].items():
            print(
                f"[spec]   {cls}: {row['rounds']} rounds, observed a="
                f"{row['acceptance_rate']:.2f}, "
                f"{row['tokens_per_round']:.2f} tok/round"
            )

    # ---- surface the adaptation loop's decisions -------------------------
    print(
        f"[adapt] windows={engine.policy.windows} "
        f"derate={engine.derate or '{}'} "
        f"events={len(engine.adaptation_events)}"
    )
    for ev in engine.adaptation_events:
        # ev.device is an int (device), an (src, dst) tuple (channel), or
        # -1 (a cluster-wide replan decision)
        if isinstance(ev.device, tuple):
            dev = f"ch{ev.device[0]}-{ev.device[1]}"
        elif ev.device < 0:
            dev = "cluster"
        else:
            dev = f"dev{ev.device}"
        print(
            f"[adapt]   w{ev.window:<3d} {ev.action:<8s} {dev:<8s}"
            f" ratio={ev.ratio:6.2f} factor {ev.old_factor:.3f}→{ev.new_factor:.3f}"
            f"  {ev.reason}"
        )
    for h in engine.replan_history:
        print(
            f"[adapt] replan (w{h['window']}): {h['reason']} — "
            f"method={h['method']} stages={h['stages']} derate={h['derate']}"
            + (
                f" link_derate={h['link_derate']}"
                if h.get("link_derate") else ""
            )
        )
    if injector is not None:
        print(f"[chaos] {len(injector.log)} injections")
        for entry in injector.log:
            e = entry["event"]
            tgt = e["device"] if e["device"] is not None else tuple(e["link"])
            print(
                f"[chaos]   s{entry['clock']:<4d} {e['kind']:<14s} "
                f"target={tgt} -> {entry['status']}"
            )
    _check_served(args, reqs)


if __name__ == "__main__":
    main()
