"""Moirai-driven inter-operator model-parallel executor (the paper's runtime).

Given a layer-granularity OpGraph placement (node → device), consecutive
co-located blocks become *stages*; each stage is a jitted function pinned to
its jax.Device, and activations move between stages with explicit
``jax.device_put`` — exactly the PyTorch runtime the paper deploys, in JAX.
Within a stage, tensor parallelism is free to apply (mesh slices); here each
Moirai device maps to one jax.Device.

Supports dense/MoE decoder-only models at ``scan_layers=False`` (per-layer
param lists — the serving configuration).  Prefill and decode keep each
stage's KV cache resident on that stage's device.  Decode accepts a
``(B,)`` ``cache_pos`` vector — ragged batches where every slot row sits at
its own depth — carried across stage boundaries unchanged.

``replace_device`` + ``from_replan`` give elastic recovery: on device
failure the engine re-plans with core.placement.replan and rebuilds stages —
weights migrate, caches are re-prefilled by the engine.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.graph import OpGraph
from repro.models import transformer
from repro.models.layers import rmsnorm, softcap

from .tracing import span


@dataclass
class Stage:
    device: Any                      # jax.Device
    layer_ids: List[int]             # model layer indices (contiguous)
    first: bool = False              # owns embedding
    last: bool = False               # owns final norm + lm head
    node_ids: List[int] = field(default_factory=list)  # OpGraph nodes in this stage


def stages_from_placement(
    graph: OpGraph,
    placement: Dict[int, int],
    devices: Sequence[Any],
    n_layers: int,
) -> List[Stage]:
    """Layer-graph nodes (embed, blocks…, lm_head) → contiguous stages.

    The layer graph is a chain: topological order maps node k to model layer
    k−1 (node 0 = embed, last = lm_head).  Moirai may interleave devices
    arbitrarily; the executor honors the order, creating a new stage at every
    device change."""
    order = graph.topo_order()
    assert len(order) == n_layers + 2, (len(order), n_layers)
    stages: List[Stage] = []
    for pos, nid in enumerate(order):
        dev = devices[placement[nid] % len(devices)]
        if pos == 0:
            stages.append(Stage(device=dev, layer_ids=[], first=True, node_ids=[nid]))
            continue
        layer_idx = pos - 1
        if pos == len(order) - 1:
            if stages[-1].device is not dev:
                stages.append(Stage(device=dev, layer_ids=[]))
            stages[-1].last = True
            stages[-1].node_ids.append(nid)
            continue
        if stages[-1].device is dev:
            stages[-1].layer_ids.append(layer_idx)
        else:
            stages.append(Stage(device=dev, layer_ids=[layer_idx]))
        stages[-1].node_ids.append(nid)
    return stages


class StageExecutor:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        stages: List[Stage],
    ):
        assert not cfg.scan_layers, "serving executor expects per-layer params"
        self.cfg = cfg
        self.stages = stages
        self._windows = transformer.layer_windows(cfg)
        self._place_params(params)
        # bounded: a long-lived executor must not retain every forward's
        # timing forever (the adaptation loop drains these per window anyway).
        # Entries are (kind, seconds) — "decode" or "prefill" — so the
        # observation windows can feed the derate calibrator DECODE samples
        # only: prefill forwards scale with prompt length, and comparing
        # them against per-token decode predictions reads as device drift
        # (spurious derates under prompt-heavy load).
        self._stage_times: List[deque] = [
            deque(maxlen=4096) for _ in stages
        ]
        self._fns: Dict[int, Callable] = {}

    # ------------------------------------------------------------------
    def _place_params(self, params):
        self.stage_params: List[Dict[str, Any]] = []
        for st in self.stages:
            sp: Dict[str, Any] = {
                "layers": [
                    jax.device_put(params["layers"][i], st.device)
                    for i in st.layer_ids
                ]
            }
            if st.first:
                sp["embed"] = jax.device_put(params["embed"], st.device)
            if st.last:
                sp["ln_final"] = jax.device_put(params["ln_final"], st.device)
                if not self.cfg.tie_embeddings:
                    sp["lm_head"] = jax.device_put(params["lm_head"], st.device)
                elif not st.first:
                    sp["embed"] = jax.device_put(params["embed"], st.device)
            self.stage_params.append(sp)

    # ------------------------------------------------------------------
    def _stage_fn(self, si: int):
        cfg = self.cfg
        st = self.stages[si]
        windows = [self._windows[i] for i in st.layer_ids]

        def run(sp, x, caches, positions, cache_pos, q_lens=None, table=None):
            new_caches = []
            if st.first:
                tokens = x
                x = jnp.take(sp["embed"], tokens, axis=0)
                if cfg.scale_embed:
                    x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
            for j, layer_p in enumerate(sp["layers"]):
                cache_j = caches[j] if caches is not None else None
                if cache_j is not None and table is not None:
                    # paged KV: one [B, pages_per_slot] table shared by every
                    # layer of every stage; pools stay per-layer per-stage
                    cache_j = dict(cache_j, table=table)
                x, nc, _ = transformer.block_apply(
                    layer_p, x, cfg,
                    positions=positions,
                    window=windows[j],   # static: the kernel specializes on it
                    kv_cache=cache_j,
                    cache_pos=cache_pos,
                    q_lens=q_lens,
                )
                if nc is not None and "table" in nc:
                    nc = {k: v for k, v in nc.items() if k != "table"}
                new_caches.append(nc)
            if st.last:
                x = rmsnorm(x, sp["ln_final"], cfg.norm_eps)
                head = sp["embed"].T if cfg.tie_embeddings else sp["lm_head"]
                x = softcap(x @ head, cfg.logit_softcap)
            return x, new_caches

        # computation follows its (committed) inputs' device placement
        return jax.jit(run)

    def _fn(self, si: int):
        fn = self._fns.get(si)
        if fn is None:
            fn = self._fns[si] = self._stage_fn(si)
        return fn

    @staticmethod
    def _step_inputs(shape, cache_pos, q_lens, page_table):
        """The per-step operands every stage shares: (positions, cache_pos,
        q_lens, page_table) as device int32 arrays."""
        b, s = shape[:2]
        cp = jnp.asarray(0 if cache_pos is None else cache_pos, jnp.int32)
        # per-row positions: row b decodes at depth cp[b] (scalar cp → all
        # rows share one depth, the classic lockstep batch)
        positions = jnp.arange(s, dtype=jnp.int32)[None] + (
            cp[:, None] if cp.ndim else cp
        )
        positions = jnp.broadcast_to(positions, (b, s))
        ql = None if q_lens is None else jnp.asarray(q_lens, jnp.int32)
        tbl = None if page_table is None else jnp.asarray(page_table, jnp.int32)
        return positions, cp, ql, tbl

    def trace_stage(
        self, si: int, x, caches=None, cache_pos=None, *,
        q_lens=None, page_table=None,
    ):
        """Stage ``si``'s jitted program traced for these step inputs, as
        :meth:`forward` would run it (``x``: the stage's input, tokens for
        stage 0).  Its ``.jaxpr`` and ``.lower()`` show what runs, e.g.
        whether the attention kernel is on the path."""
        st_caches = caches[si] if caches is not None else None
        return self._fn(si).trace(
            self.stage_params[si], x, st_caches,
            *self._step_inputs(x.shape, cache_pos, q_lens, page_table),
        )

    # ------------------------------------------------------------------
    def _zero_caches(self, shape_of):
        """Per stage, per layer, one zero array for each of the model's
        cache entries (``cfg.cache_widths()``: K and V, or MLA's latent),
        of ``shape_of(heads, width)``, on that stage's device."""
        dt = jnp.dtype(self.cfg.dtype)
        widths = self.cfg.cache_widths()
        return [
            [
                {
                    name: jax.device_put(jnp.zeros(shape_of(heads, w), dt), st.device)
                    for name, (heads, w) in widths.items()
                }
                for _ in st.layer_ids
            ]
            for st in self.stages
        ]

    def init_caches(self, batch: int, max_len: int):
        return self._zero_caches(lambda heads, w: (batch, max_len, heads, w))

    def init_paged_caches(self, num_pages: int, page_tokens: int):
        """Paged pools: per stage, per layer, ``[heads, num_pages+1, P, w]``
        (head-major) for each cache entry — K and V of the KV heads, or
        MLA's one latent pool — on that stage's device (the +1 is the
        reserved trash page).  The page table is host-owned
        (``serving.kv_pool.KVPool``) and rides into :meth:`forward` as
        ``page_table`` each step."""
        return self._zero_caches(
            lambda heads, w: (heads, num_pages + 1, page_tokens, w))

    def copy_pages(self, caches, pairs):
        """Copy-on-write support: materialize page copies ``(src, dst)`` in
        every stage's every layer pool (K and V, or the latent pool).
        Called at admission when a request's prompt diverges inside a shared
        prefix page; the table update itself is host-side (KVPool)."""
        if not pairs:
            return caches
        with span("executor.copy_pages"):
            src = jnp.asarray([s for s, _ in pairs], jnp.int32)
            dst = jnp.asarray([d for _, d in pairs], jnp.int32)
            return [
                [
                    {key: pool.at[:, dst].set(pool[:, src]) for key, pool in c.items()}
                    for c in st_caches
                ]
                for st_caches in caches
            ]

    def forward(
        self,
        tokens: jax.Array,            # [B, S] (prefill) or [B, 1] (decode)
        caches=None,
        cache_pos=None,               # int scalar, or (B,) int vector (ragged
                                      # decode: one cache depth per slot row)
        *,
        kind: Optional[str] = None,   # "decode" | "prefill" | "fused" sample
                                      # tag; None infers from the token count
        q_lens=None,                  # (B,) valid tokens per row — the fused
                                      # mixed-batch ragged shape (decode rows
                                      # 1, prefill chunks n, idle rows 0)
        fused_decode_frac: Optional[List[float]] = None,
                                      # kind="fused": predicted decode share
                                      # of each stage's wall time — one fused
                                      # forward records a ("decode", dt·f) AND
                                      # a ("prefill", dt·(1−f)) sample so the
                                      # calibrator's windows stay clean
        page_table=None,              # [B, pages_per_slot] int32 — paged-KV
                                      # table (caches hold page pools)
    ):
        if kind is None:
            kind = "prefill" if tokens.shape[1] > 1 else "decode"
        elif kind not in ("decode", "prefill", "fused"):
            raise ValueError(
                f"kind must be 'decode', 'prefill' or 'fused', got {kind!r}"
            )
        # per stage: executor.put (stage 0 also makes the shared step
        # inputs), executor.launch (dispatch, or a retrace and compile),
        # executor.wait (the host blocked on the device); the stage's
        # recorded time runs from the start of its put to the end of its wait
        step_in = None
        x = tokens
        new_caches = []
        with span("executor.forward"):
            for si, st in enumerate(self.stages):
                t0 = time.perf_counter()
                with span("executor.put", stage=si):
                    if step_in is None:
                        step_in = self._step_inputs(
                            tokens.shape, cache_pos, q_lens, page_table
                        )
                    x = jax.device_put(x, st.device)   # inter-stage data flow
                st_caches = caches[si] if caches is not None else None
                with span("executor.launch", stage=si):
                    x, nc = self._fn(si)(
                        self.stage_params[si], x, st_caches, *step_in
                    )
                with span("executor.wait", stage=si):
                    x.block_until_ready()
                dt = time.perf_counter() - t0
                if kind == "fused":
                    # split the single wall-clock sample by the cost model's
                    # predicted decode share so neither op class pollutes the
                    # other's observation window (prefill work scales with
                    # the chunk; scoring it as decode reads as device drift)
                    f = 1.0 if fused_decode_frac is None else float(fused_decode_frac[si])
                    f = min(max(f, 0.0), 1.0)
                    if f > 0.0:
                        self._stage_times[si].append(("decode", dt * f))
                    if f < 1.0:
                        self._stage_times[si].append(("prefill", dt * (1.0 - f)))
                else:
                    self._stage_times[si].append((kind, dt))
                new_caches.append(nc)
        return x, new_caches

    def _times(self, rec, kind: Optional[str]) -> List[float]:
        return [t for k, t in rec if kind is None or k == kind]

    def stage_times(self, kind: Optional[str] = None) -> List[List[float]]:
        """Per-stage wall-clock seconds of recent forward calls (bounded
        ring, most recent last; copies — mutating the return value cannot
        corrupt the recorder).  ``kind`` filters to "decode" or "prefill"
        samples (None = all)."""
        return [self._times(rec, kind) for rec in self._stage_times]

    def drain_stage_times(self, kind: Optional[str] = None) -> List[List[float]]:
        """Return the recorded per-stage times and RESET the recorders —
        each call yields only the samples since the previous drain (the
        engine's observation windows).  ``kind`` selects which samples are
        RETURNED (None = all); the reset always clears everything, so one
        window's prefill samples can never leak into a later window."""
        out = [self._times(rec, kind) for rec in self._stage_times]
        for rec in self._stage_times:
            rec.clear()
        return out


def stats_from_times(times: Sequence[float]) -> Dict[str, float]:
    """mean/p95/n summary of one stage's observed latencies; the single
    aggregation used for executor-recorded and externally-injected samples."""
    import numpy as np

    if not times:
        return {"mean": 0.0, "p95": 0.0, "n": 0}
    arr = np.asarray(times, dtype=np.float64)
    return {
        "mean": float(arr.mean()),
        "p95": float(np.percentile(arr, 95)),
        "n": len(times),
    }
