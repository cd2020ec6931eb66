"""Multi-instance serving cluster over real JAX engines.

This is the control plane of DESIGN §3 running against actual model
compute: N in-process Engine instances serving one model, grouped into
length-specialized stages (PipelinePlan). All scheduling decisions —
round-robin-within-stage arrival routing (§3.2), growth-triggered
handover with sender/receiver bid-ask negotiation, intra-stage
rebalancing, boundary refinement (all Fig. 15/16 ablation modes), §5
flow control — come from the shared, backend-agnostic core
(`repro.control.plane.ControlPlane`), the same code the discrete-event
simulator drives. This server only supplies the mechanisms: step-
synchronous time (every engine advances one continuous-batching
iteration per tick) and real KV-piece migration between engines.

The serving API is open-loop: `submit_at(req, step)` builds an arrival
schedule (e.g. replayed from a `sim/workload.py` trace via
`requests_from_trace`), `step()`/`run()` advance it, an optional
`on_token` callback streams every generated token, and `run(drain=True)`
keeps stepping until everything submitted has finished.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.control import (MIG_COMPLETED, MIG_FAILED, MIG_STARTED, XFER_OK,
                           XFER_STALL, ControlConfig, ControlPlane,
                           FaultInjector, FaultSpec, ReqView)
from repro.core.partition import PipelinePlan
from repro.core.qoe import QoEModel
from repro.kernels.cost import promote_cost_tokens
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest, State
from repro.sim.metrics import class_slo_summary, fault_summary
from repro.sim.workload import Request

TokenCallback = Callable[[ServeRequest, int], None]


@dataclasses.dataclass
class ServerConfig:
    policy: str = "cascade"            # cascade | round-robin | least-loaded
    refinement: str = "adaptive"       # adaptive | quantity | memory | none
    balancing: str = "full"            # full | inter-stage | rr
    refine_every: int = 16             # steps
    balance_every: int = 8
    max_migrations_per_step: int = 3   # §5 concurrency cap
    seed: int = 0
    attn_backend: Optional[str] = None  # dense | grid | flat | fused | None=auto
    kv_dtype: str = "bf16"             # bf16 | int8 (DESIGN.md §Quantized KV)
    # SLO-tiered preemptive scheduling (DESIGN.md §SLO scheduling).
    # ``preemption=False`` restores bit-identical FCFS queues. With
    # uniform-class traffic and distinct arrival steps the SLO queue
    # order equals FCFS and no preemption can fire, so the default is
    # safe for legacy traces.
    preemption: bool = True
    slo_scale: float = 1.0             # paper §6.4 SLO-scale sweep knob
    slo_time_scale: float = 1.0        # engine steps per abstract SLO second
    # Multi-tier KV (DESIGN.md §Multi-tier KV): host-RAM tier capacity in
    # tokens per engine. 0 = tiering off — reclaim drops cached chains
    # exactly as before (bit-identical to the pre-tier server); the
    # launcher defaults this ON with a conservative budget.
    host_kv_budget: int = 0
    # ---- fault tolerance (DESIGN.md §Fault tolerance) ----
    # None = fault-free: no heartbeats/liveness run, behavior is
    # bit-identical to the pre-fault server. Spec times are in STEPS.
    faults: Optional[FaultSpec] = None
    suspect_after_steps: int = 3       # heartbeat-free steps -> suspect
    dead_after_steps: int = 6          # -> dead, residents recovered
    migration_timeout_steps: int = 4   # wire deadline for one transfer
    redispatch_budget: int = 2         # dead-engine recoveries per request


class EngineView:
    """`repro.control.protocol.InstanceView` over a real engine."""

    def __init__(self, eng):
        self.eng = eng
        self.id = eng.id

    def load(self) -> float:
        return self.eng.load()

    def free_tokens(self) -> float:
        return float(self.eng.free_tokens())

    def used_tokens(self) -> float:
        return float(self.eng.used_tokens())

    def queued_tokens(self) -> float:
        return float(self.eng.queued_tokens())

    def capacity_weight(self) -> float:
        """Instance-units this engine counts for — its tensor-parallel
        ways (DESIGN.md §Sharded serving). FakeEngine harnesses without
        a ``tp`` attribute weigh 1."""
        return float(getattr(self.eng, "tp", 1) or 1)

    def requests(self) -> List[ReqView]:
        return [ReqView(r, r.req_id, float(len(r.prompt)), float(r.length),
                        ctx_done=float(r.ctx_done),
                        ctx_total=float(r.prefill_target_len),
                        cached_tokens=float(r.cached_tokens),
                        slo_class=r.slo_class)
                for r in self.eng.slots if r is not None]

    def prefix_digests(self) -> frozenset:
        fn = getattr(self.eng, "prefix_digests", None)
        return fn() if fn is not None else frozenset()

    def tiered_digests(self):
        """digest -> "device"|"host" for tier-aware warm routing. Engines
        without a host tier (or FakeEngines without the hook) advertise
        everything as device-resident."""
        fn = getattr(self.eng, "tiered_digests", None)
        if fn is not None:
            return fn()
        return {d: "device" for d in self.prefix_digests()}

    def request_view(self):
        return self.eng.request_view()

    def has_request(self, req: ServeRequest) -> bool:
        return (req.state is State.RUNNING and req.engine_id == self.id
                and any(r is req for r in self.eng.slots))

    def can_accept(self, req: ServeRequest) -> bool:
        return self.eng.can_accept(req)

    def all_requests(self) -> List[ReqView]:
        """Every resident — slotted, waiting, parked. Dead-engine recovery
        re-dispatches all of them (a queued request dies with its engine
        just as surely as a running one)."""
        reqs = [r for r in self.eng.slots if r is not None]
        reqs += list(getattr(self.eng, "waiting", ()))
        for p in getattr(self.eng, "parked", ()):
            reqs.append(getattr(p, "req", p))   # Engine parks _Parked entries
        out, seen = [], set()
        for r in reqs:
            if id(r) in seen:
                continue
            seen.add(id(r))
            out.append(ReqView(r, r.req_id, float(len(r.prompt)),
                               float(r.length), ctx_done=float(r.ctx_done),
                               ctx_total=float(r.prefill_target_len),
                               cached_tokens=float(r.cached_tokens),
                               slo_class=r.slo_class))
        return out


class _ServerOps:
    """`repro.control.protocol.ClusterOps` over the engine pool: dispatch
    is an engine submit, migration is a synchronous export → import →
    evict of the request's actual KV piece."""

    def __init__(self, server: "MILSServer"):
        self.server = server

    def dispatch(self, req: ServeRequest, instance_id: int) -> None:
        self.server.engines[instance_id].submit(req)

    def start_migration(self, req: ServeRequest, src_id: int,
                        dst_id: int) -> str:
        server = self.server
        if src_id in server.crashed or dst_id in server.crashed:
            return MIG_FAILED      # either endpoint's process is gone
        if server.injector is not None:
            fate = server.injector.transfer_event(req.req_id)
            if fate != XFER_OK:
                # lost/stalled wire: mirror the simulator's ASYNC failure
                # sequence — report MIG_STARTED now (the plane logs
                # "migrate", keeping decision parity) and deliver the
                # failure when the deadline expires; the request never
                # leaves the source
                horizon = server.cfg.migration_timeout_steps * (
                    2 if fate == XFER_STALL else 1)
                server._doomed.append((server.steps + horizon, req.req_id))
                return MIG_STARTED
        src = server.engines[src_id]
        dst = server.engines[dst_id]
        slot = req.slot
        if slot is None or src.slots[slot] is not req:
            return MIG_FAILED
        with _span("plane.migrate", req=req.req_id, src=src_id, dst=dst_id):
            _, piece, _ = src.export_slot(slot)
            if not dst.import_request(req, piece):
                return MIG_FAILED
            src.evict_slot(slot)
        return MIG_COMPLETED

    def set_boundary(self, stage_idx: int, hi: float) -> None:
        pass                        # the core's bounds are authoritative

    # ---- fault tolerance (DESIGN.md §Fault tolerance) --------------------
    def redispatch(self, req: ServeRequest, instance_id: int) -> bool:
        """Recover a resident of a dead engine: its KV died with the
        process, so replay prompt + generated-so-far through chunked
        prefill on ``instance_id`` — the same resume machinery recompute
        preemption uses (Engine._finish_resume), so the continuation is
        bit-identical to a never-crashed run."""
        dst = self.server.engines[instance_id]
        req.redispatches += 1
        req.slot = None
        req.engine_id = None
        req.ctx_done = 0
        req.cached_tokens = 0
        if req.generated:
            if not getattr(dst, "chunked_prefill", False):
                return False       # mid-decode resume needs chunked prefill
            req.prefill_target = len(req.prompt) + len(req.generated) - 1
            req.resume_tokens = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.generated[:-1], np.int32)])
        else:
            req.prefill_target = None
            req.resume_tokens = None
        req.state = State.WAITING
        dst.submit(req)
        return True

    def fail_request(self, req: ServeRequest) -> None:
        req.failed = True
        req.state = State.FINISHED
        req.finish_step = self.server.steps
        # completion of a sort: the drain loop must terminate
        self.server.finished.append(req)

    def instance_down(self, instance_id: int) -> None:
        # replace the carcass with a fresh engine so a later rejoin
        # starts empty (the core snapshotted the residents already)
        self.server._reset_engine(instance_id)


class MILSServer:
    def __init__(self, model, params, plan: PipelinePlan,
                 qoe: Optional[QoEModel], cfg: ServerConfig, *,
                 max_slots: int = 4, max_seq: int = 256,
                 paged: Optional[bool] = None, block_size: int = 16,
                 device_resident: Optional[bool] = None,
                 attn_backend: Optional[str] = None,
                 prefill_token_budget: Optional[int] = None,
                 chunked_prefill: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 host_kv_budget: Optional[int] = None,
                 tp: Any = 1,
                 engine_factory: Optional[Callable[[int], Any]] = None,
                 on_token: Optional[TokenCallback] = None):
        self.cfg = cfg
        self.plan = plan
        self.on_token = on_token
        # constructor kwargs override the ServerConfig defaults
        attn_backend = attn_backend or cfg.attn_backend
        kv_dtype = kv_dtype or cfg.kv_dtype
        host_kv_budget = (cfg.host_kv_budget if host_kv_budget is None
                          else int(host_kv_budget))
        # tensor parallelism (DESIGN.md §Sharded serving): an int gives
        # every engine the same TP ways; a sequence gives engine i
        # tp[i] — a HETEROGENEOUS cluster (e.g. (2, 1, 1)) whose capacity
        # weights the control plane uses for stage claiming and load
        # normalization. Engines own disjoint device prefixes-by-mesh.
        if isinstance(tp, (list, tuple)):
            tps = [int(x) for x in tp]
            assert len(tps) == plan.num_instances, \
                f"tp has {len(tps)} entries for {plan.num_instances} engines"
        else:
            tps = [int(tp)] * plan.num_instances
        self.tps = tps
        if engine_factory is None:
            def engine_factory(i):
                return Engine(i, model, params, max_slots=max_slots,
                              max_seq=max_seq, paged=paged,
                              block_size=block_size,
                              device_resident=device_resident,
                              attn_backend=attn_backend,
                              prefill_token_budget=prefill_token_budget,
                              chunked_prefill=chunked_prefill,
                              prefix_cache=prefix_cache,
                              kv_dtype=kv_dtype,
                              host_kv_budget=host_kv_budget,
                              preemption=cfg.preemption,
                              slo_time_scale=cfg.slo_time_scale,
                              tp=tps[i])
        self._engine_factory = engine_factory
        self.engines = [engine_factory(i)
                        for i in range(plan.num_instances)]
        self.plane = ControlPlane(
            plan, qoe,
            ControlConfig(policy=cfg.policy, refinement=cfg.refinement,
                          balancing=cfg.balancing,
                          max_migrations_per_tick=cfg.max_migrations_per_step,
                          seed=cfg.seed,
                          suspect_after=float(cfg.suspect_after_steps),
                          dead_after=float(cfg.dead_after_steps),
                          redispatch_budget=cfg.redispatch_budget),
            ops=_ServerOps(self),
            instances=[EngineView(e) for e in self.engines])
        self.steps = 0
        self.finished: List[ServeRequest] = []
        self.submitted = 0
        # ---- fault state (DESIGN.md §Fault tolerance) ----
        self.injector = (FaultInjector(cfg.faults)
                         if cfg.faults is not None else None)
        self.crashed: Dict[int, int] = {}        # engine id -> crash step
        self.downtime_steps: Dict[int, int] = {}
        self._doomed: List[Tuple[int, int]] = []  # (fail_at_step, req_id)
        # open-loop arrival schedule: (step, seq, request)
        self._schedule: List[Tuple[int, int, ServeRequest]] = []
        self._seq = 0
        self._emitted: Dict[int, int] = {}   # req_id -> tokens streamed

    # ---- observability -------------------------------------------------------
    @property
    def stage_bounds(self) -> List[Tuple[float, float]]:
        return self.plane.bounds()

    @property
    def migrations(self) -> int:
        return self.plane.migrations

    # ---- intake --------------------------------------------------------------
    def _prefix_hint(self, req: ServeRequest):
        """(head_digest, best cached tokens, promote price in token units)
        across the engine pool — the dispatch hint cache-aware routing
        consumes. Engines without a prefix cache (or FakeEngines without
        the hook) contribute nothing. Tier-aware engines return a 3-tuple
        whose third element counts host-tier blocks the hit would have to
        promote; legacy 2-tuple hints price as all-device. Ties on cached
        tokens prefer the cheaper (device-warm) instance, and the SAME
        pure pricing fn (`kernels.cost.promote_cost_tokens`) runs in the
        simulator's CascadePolicy so decision logs stay comparable."""
        digest, cached, price = None, 0.0, 0.0
        for eng in self.engines:
            fn = getattr(eng, "prefix_hint", None)
            if fn is None:
                continue
            out = fn(req)
            d, c, promo = out if len(out) == 3 else (out[0], out[1], 0)
            p = promote_cost_tokens(promo, getattr(eng, "block_size", 0))
            if d is not None:
                digest = d
            if (float(c), -p) > (cached, -price):
                cached, price = float(c), p
        return digest, cached, price

    def submit(self, req: ServeRequest) -> None:
        """Closed-loop submission: the request arrives now."""
        req.arrival_step = self.steps
        req.t_submit = time.perf_counter()
        self.submitted += 1
        with _span("server.route", req=req.req_id):
            digest, cached, price = self._prefix_hint(req)
            self.plane.submit(req, req.req_id, float(len(req.prompt)),
                              cached_tokens=cached, prefix_digest=digest,
                              promote_cost_tokens=price,
                              slo_class=req.slo_class)

    def submit_at(self, req: ServeRequest, step: int) -> None:
        """Open-loop submission: the request arrives at ``step`` (replays
        a workload trace's arrival process in server time)."""
        self.submitted += 1
        heapq.heappush(self._schedule, (int(step), self._seq, req))
        self._seq += 1

    def _release_arrivals(self) -> None:
        while self._schedule and self._schedule[0][0] <= self.steps:
            _, _, req = heapq.heappop(self._schedule)
            req.arrival_step = self.steps
            req.t_submit = time.perf_counter()
            digest, cached, price = self._prefix_hint(req)
            self.plane.submit(req, req.req_id, float(len(req.prompt)),
                              cached_tokens=cached, prefix_digest=digest,
                              promote_cost_tokens=price,
                              slo_class=req.slo_class)

    # ---- token streaming -----------------------------------------------------
    def _stream(self, reqs: Sequence[ServeRequest]) -> None:
        if self.on_token is None:
            return
        for r in reqs:
            n = self._emitted.get(r.req_id, 0)
            for tok in r.generated[n:]:
                self.on_token(r, tok)
            self._emitted[r.req_id] = len(r.generated)

    # ---- faults (DESIGN.md §Fault tolerance) ---------------------------------
    def _crash(self, iid: int) -> None:
        """Scripted hard-kill: the engine stops stepping and heartbeating;
        the plane's liveness machinery discovers the death and recovers
        the residents."""
        self.crashed[iid] = self.steps
        # flag the carcass so the conftest drain-leak fixture skips it
        try:
            self.engines[iid]._faulted = True
        except AttributeError:
            pass

    def _reset_engine(self, iid: int) -> None:
        """Swap in a fresh engine (ClusterOps.instance_down / rejoin):
        the old process' state is unreachable, a rejoin starts empty."""
        try:
            self.engines[iid]._faulted = True
        except AttributeError:
            pass
        fresh = self._engine_factory(iid)
        self.engines[iid] = fresh
        self.plane.instances[iid] = EngineView(fresh)

    def _revive(self, iid: int) -> None:
        self._reset_engine(iid)
        self.crashed.pop(iid, None)
        # the plane learns of the rejoin from the next heartbeat

    def _inject_faults(self) -> None:
        if self.injector is None:
            return
        # all_crashes folds correlated rack events into the per-instance
        # schedule — several engines can die in the same step
        for iid, at in self.cfg.faults.all_crashes:
            if int(at) == self.steps and iid not in self.crashed:
                self._crash(iid)
        for iid, at in self.cfg.faults.rejoins:
            if int(at) == self.steps and iid in self.crashed:
                self._revive(iid)
        # deliver due wire deadlines (lost/stalled transfers)
        due = [r for s, r in self._doomed if s <= self.steps]
        self._doomed = [(s, r) for s, r in self._doomed if s > self.steps]
        for rid in due:
            self.plane.migration_failed(rid)

    def _engine_runs_this_step(self, eng) -> bool:
        if eng.id in self.crashed:
            self.downtime_steps[eng.id] = \
                self.downtime_steps.get(eng.id, 0) + 1
            return False
        if self.injector is not None:
            f = self.injector.slowdown(eng.id)
            if f > 1.0 and self.steps % max(int(round(f)), 1) != 0:
                return False       # slow instance: skips iterations
        return True

    # ---- main loop -----------------------------------------------------------
    def step(self) -> List[ServeRequest]:
        self._release_arrivals()
        self.steps += 1
        self._inject_faults()
        done: List[ServeRequest] = []
        for eng in self.engines:
            if not self._engine_runs_this_step(eng):
                continue
            fin = eng.step()
            done.extend(fin)
            with _span("server.stream"):
                self._stream(eng.active())
                self._stream(fin)
        self.finished.extend(done)
        for r in done:
            self._emitted.pop(r.req_id, None)
        if self.cfg.policy == "cascade":
            with _span("plane.tick") as sp:
                mig0 = self.plane.migrations
                self._tick()
                if _span.is_enabled():
                    sp.set_metadata(handovers=self.plane.migrations - mig0)
        return done

    def _tick(self) -> None:
        """The cascade control plane's work after the engines' steps:
        liveness, growth handover, balance, boundary refinement, and the
        deferred offers (migrations run inside, as KV export -> import)."""
        self.plane.begin_tick()
        if self.cfg.faults is not None:
            # liveness runs only on fault-aware servers, so legacy runs
            # stay bit-identical to the pre-fault server
            for eng in self.engines:
                if eng.id not in self.crashed:
                    self.plane.heartbeat(eng.id, float(self.steps))
            self.plane.check_liveness(float(self.steps))
        self.plane.handover_all()
        if self.steps % self.cfg.balance_every == 0:
            self.plane.balance()
        if self.steps % self.cfg.refine_every == 0:
            self.plane.refine()
        # retry offers deferred by §5 flow control / the tick budget —
        # without this an offer put back in a receiver queue would only be
        # retried if a later offer happened to land on that receiver
        self.plane.pump_all()

    def run(self, requests: Sequence[ServeRequest] = (),
            max_steps: int = 2000, drain: bool = True) -> List[ServeRequest]:
        """Drive the arrival schedule (plus any ``requests`` submitted
        immediately). With ``drain`` (default) keep stepping until every
        submitted request finished; otherwise stop once the schedule is
        exhausted."""
        for r in requests:
            self.submit(r)
        while self.steps < max_steps:
            if not self._schedule and (not drain
                                       or len(self.finished)
                                       >= self.submitted):
                break
            self.step()
        if drain and len(self.finished) >= self.submitted:
            # drained server = leak check: every live engine must hold no
            # requests and no allocator state beyond reclaimable cache
            for eng in self.engines:
                if eng.id in self.crashed:
                    continue
                chk = getattr(eng, "check_drained", None)
                if chk is not None:
                    chk(strict=True)
        return self.finished

    # ---- metrics -------------------------------------------------------------
    def summary(self) -> Dict[str, float]:
        fin = self.finished
        if not fin:
            return {"finished": 0}
        # rejected/failed requests never finished normal service — folding
        # their fabricated timestamps into the means would fake latencies
        served = [r for r in fin if not r.rejected and not r.failed]
        out: Dict[str, float] = {
            "finished": len(fin),
            "steps": self.steps,
            "migrations": self.migrations,
            "tokens_out": int(sum(e.tokens_out for e in self.engines)),
        }
        # failure accounting through the SAME formula the simulator
        # reports (sim.metrics.fault_summary)
        out.update(fault_summary(
            ((r.rejected, r.failed, r.redispatches) for r in fin),
            retries=self.plane.retries,
            downtime={i: float(s) for i, s in self.downtime_steps.items()
                      if s}))
        # per-stage-pair migration counts (handover vs. rebalance visibility)
        for (a, b), n in sorted(self.plane.migrations_by_stage.items()):
            out[f"migrations_s{a}_to_s{b}"] = n
        if served:
            ttft = np.asarray([r.first_token_step - r.arrival_step
                               for r in served], np.float64)
            e2e = np.asarray([r.finish_step - r.arrival_step
                              for r in served], np.float64)
            # tail latency is the paper's headline claim — report the
            # distribution, not just the mean (mirrors sim/metrics.py)
            for name, arr in (("ttft_steps", ttft), ("e2e_steps", e2e)):
                out[f"{name}_mean"] = float(arr.mean())
                for p in (50, 95, 99):
                    out[f"{name}_p{p}"] = float(np.percentile(arr, p))
        # the same on the host clock, in seconds from submission: queue
        # wait to first admission, and time to the first token on the host
        timed = [r for r in served if r.t_submit is not None
                 and r.t_admit is not None and r.t_first_token is not None]
        if timed:
            for name, arr in (
                    ("queue_wait_s", [r.t_admit - r.t_submit for r in timed]),
                    ("ttft_s", [r.t_first_token - r.t_submit
                                for r in timed])):
                for p in (50, 95):
                    out[f"{name}_p{p}"] = float(np.percentile(arr, p))
        # per-class SLO attainment + goodput-under-SLO, through the SAME
        # formula the simulator reports (sim.metrics.class_slo_summary) —
        # ``slo_time_scale`` converts the abstract class deadlines into
        # steps, ``slo_scale`` is the paper's SLO-scale sweep knob
        entries = []
        for r in served:
            ttft_r = float(r.first_token_step - r.arrival_step)
            tpot_r = (float(r.finish_step - r.first_token_step)
                      / max(len(r.generated) - 1, 1))
            entries.append((r.slo_class, ttft_r, tpot_r, len(r.generated)))
        per = class_slo_summary(entries, float(self.steps),
                                scale=self.cfg.slo_scale,
                                time_scale=self.cfg.slo_time_scale)
        for cls, d in sorted(per.items()):
            out[f"slo_{cls}_attainment"] = d["attainment"]
            out[f"slo_{cls}_goodput_tok_step"] = d["goodput_tok_s"]
            out[f"slo_{cls}_requests"] = d["requests"]
        # getattr: custom engine_factory backends (FakeEngine parity
        # harnesses) may predate the preemption counters
        out["preemptions"] = sum(getattr(e, "preemptions", 0)
                                 for e in self.engines)
        out["preempt_recomputes"] = sum(getattr(e, "preempt_recomputes", 0)
                                        for e in self.engines)
        out["resumes"] = sum(getattr(e, "resumes", 0) for e in self.engines)
        out["tpot_skipped"] = sum(getattr(e, "tpot_skipped", 0)
                                  for e in self.engines)
        # multi-tier KV traffic (DESIGN.md §Multi-tier KV)
        for k in ("cache_demotions", "cache_drops", "cache_promotions",
                  "promoted_blocks_total"):
            out[k] = sum(getattr(e, k, 0) for e in self.engines)
        return out


def requests_from_trace(trace: Sequence[Request], *, vocab_size: int,
                        steps_per_second: float = 1.0,
                        max_seq: Optional[int] = None,
                        seed: int = 0) -> List[Tuple[ServeRequest, int]]:
    """Convert a `sim/workload.py` trace into (ServeRequest, arrival_step)
    pairs so the server replays the exact workload the simulator consumes:
    input_len becomes a random prompt of that length, output_len the token
    budget, and Poisson arrival times map to steps at ``steps_per_second``.
    ``max_seq`` caps lengths to what a small real engine can hold (the
    sim's 128K-context tail does not fit a reduced test model).

    Traces carrying shared-prefix groups (``Request.prefix_group >= 0``,
    from ``sim.workload.shared_prefix_spec``) are replayed with LITERAL
    shared prefixes: every request in a group starts with the same token
    block, so the real engine's content-hashed prefix cache hits exactly
    where the simulator's group-granular model does."""
    rng = np.random.default_rng(seed)
    prefixes: Dict[int, np.ndarray] = {}
    out = []
    for r in trace:
        plen, new = int(r.input_len), int(r.output_len)
        pg = getattr(r, "prefix_group", -1)
        pfx_len = int(getattr(r, "prefix_len", 0)) if pg >= 0 else 0
        if max_seq is not None:
            plen = max(1, min(plen, max_seq // 2))
            new = max(1, min(new, max_seq - plen - 1))
            pfx_len = min(pfx_len, max(plen - 1, 0))
        prompt = rng.integers(0, vocab_size, plen).astype(np.int32)
        if pfx_len > 0:
            if pg not in prefixes:
                # one draw at the group's FULL prefix length: a capped
                # replay still shares the same leading tokens
                prefixes[pg] = rng.integers(
                    0, vocab_size,
                    int(getattr(r, "prefix_len", 0))).astype(np.int32)
            prompt[:pfx_len] = prefixes[pg][:pfx_len]
        req = ServeRequest(r.req_id, prompt, new)
        req.prefix_group = pg
        req.prefix_len = pfx_len
        req.slo_class = getattr(r, "slo_class", "standard")
        out.append((req, int(round(r.arrival * steps_per_second))))
    return out
