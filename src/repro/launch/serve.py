"""Serving launcher: a CascadeInfer MILS cluster over real JAX engines.

Replays a `sim/workload.py` trace open-loop against the real engines —
the same arrival process the discrete-event simulator consumes — through
the shared control plane (`repro.control`).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --engines 4 --requests 12
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.core.partition import PipelinePlan, Stage
from repro.core.qoe import QoEModel
from repro.launch.mesh import make_tp_mesh
from repro.launch.shardings import serving_param_spec_tree
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.sched import assign_classes, parse_class_mix
from repro.serving.server import (MILSServer, ServerConfig,
                                  requests_from_trace)
from repro.sim.workload import WorkloadSpec, generate

# fixed in-checkout home of the persistent compilation cache (listed in
# .gitignore); a fixed path matters, since it is part of the cache key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache — the one place the repo
    sets it. ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the
    cache lives in :data:`COMPILE_CACHE_DIR`. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def default_plan(num_engines: int, max_seq: int) -> PipelinePlan:
    """Two length stages splitting the engine pool (bootstrapping plan;
    production planning uses core.partition on profiled stats)."""
    if num_engines == 1:
        return PipelinePlan([Stage(0.0, float("inf"), 1)], 0.0)
    half = num_engines // 2
    return PipelinePlan(
        [Stage(0.0, max_seq / 4, num_engines - half),
         Stage(max_seq / 4, float("inf"), half)], 0.0)


def init_params(model, seed: int = 0, tp: int = 1):
    """Random weights from ``seed``. With ``tp > 1`` every weight is
    created straight into its serving sharding over :func:`make_tp_mesh`
    (DESIGN.md §Sharded serving), so a model that needs several chips is
    never whole on one of them."""
    key = jax.random.PRNGKey(seed)
    if tp == 1:
        return jax.jit(model.init)(key)
    mesh = make_tp_mesh(tp)
    specs = serving_param_spec_tree(jax.eval_shape(model.init, key), tp)
    return jax.jit(model.init, out_shardings=jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))(key)


def build_server(cfg: ModelConfig, server_cfg: ServerConfig, *,
                 engines: int = 4, tp: Any = 1,
                 max_seq: int = 128, max_slots: int = 3,
                 params: Optional[Any] = None, **engine_kwargs) -> MILSServer:
    """A MILS cluster of ``engines`` engines serving ``cfg`` — any width,
    from a ``reduced()`` test config to a published one — behind the
    bootstrapping :func:`default_plan`. Weights are random from seed 0
    unless ``params`` is given; a uniform ``tp > 1`` initialises them
    sharded (:func:`init_params`). ``engine_kwargs`` go to every
    :class:`~repro.serving.engine.Engine`."""
    model = build_model(cfg)
    tps = list(tp) if isinstance(tp, (list, tuple)) else [int(tp)] * engines
    if params is None:
        params = init_params(model, 0, tps[0] if len(set(tps)) == 1 else 1)
    qoe = QoEModel(np.array([1e-3, 1e-4, 1e-6, 0.0, 1e-6]))
    return MILSServer(model, params, default_plan(engines, max_seq), qoe,
                      server_cfg, tp=tp, max_slots=max_slots,
                      max_seq=max_seq, **engine_kwargs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--engines", type=int, default=4)
    ap.add_argument("--tp", default="1",
                    help="tensor-parallel degree per engine: a single "
                         "int ('2') shards every engine over that many "
                         "devices, or a comma list ('2,1,1,1') for a "
                         "heterogeneous cluster (DESIGN.md §Sharded "
                         "serving; needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N or "
                         "real devices)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--policy", default="cascade",
                    choices=["cascade", "round-robin", "least-loaded"])
    ap.add_argument("--refinement", default="adaptive",
                    choices=["adaptive", "quantity", "memory", "none"])
    ap.add_argument("--balancing", default="full",
                    choices=["full", "inter-stage", "rr"])
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-slots", type=int, default=3)
    ap.add_argument("--attn-backend", default=None,
                    choices=["dense", "grid", "flat", "fused"],
                    help="paged attention backend (default: auto — the "
                         "fused mixed-iteration kernel on TPU, dense XLA "
                         "elsewhere; see DESIGN.md §Decode hot path and "
                         "§Fused mixed-iteration attention)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8"],
                    help="paged KV block pool dtype — int8 halves KV "
                         "bytes (~2x resident requests; needs the fused "
                         "or dense backend; DESIGN.md §Quantized KV "
                         "blocks)")
    ap.add_argument("--host-loop", action="store_true",
                    help="use the legacy host-driven engine step loop")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prompt-chunk tokens packed per mixed iteration "
                         "(DESIGN.md §Chunked prefill; default 256)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="monolithic whole-prompt prefill (the §2.1 "
                         "head-of-line baseline)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="refcounted prefix-shared KV pool (DESIGN.md "
                         "§Prefix cache; the default)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prefix sharing — the bit-parity "
                         "legacy allocator path")
    ap.add_argument("--host-kv-budget", type=int, default=4096,
                    help="host-RAM KV tier capacity in tokens per engine "
                         "(DESIGN.md §Multi-tier KV): evicted prefix "
                         "chains demote here instead of dropping, and "
                         "hits promote back asynchronously. 0 reproduces "
                         "the drop-on-reclaim allocator bit-exactly "
                         "(default: a conservative 4096)")
    ap.add_argument("--no-kv-tiering", dest="host_kv_budget",
                    action="store_const", const=0,
                    help="disable the host KV tier (same as "
                         "--host-kv-budget 0)")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="workload arrivals/s, replayed at 1 step/s")
    ap.add_argument("--slo-class-mix", default=None,
                    help="SLO service-class mix for the replayed trace, "
                         "e.g. 'interactive:0.5,standard:0.3,batch:0.2' "
                         "(classes: repro.sched.SLO_CLASSES; default: "
                         "all standard)")
    ap.add_argument("--preemption", dest="preemption",
                    action="store_true", default=True,
                    help="SLO-tiered preemptive scheduling (DESIGN.md "
                         "§SLO scheduling; the default)")
    ap.add_argument("--no-preemption", dest="preemption",
                    action="store_false",
                    help="disable preemption — bit-parity FCFS queues")
    ap.add_argument("--slo-scale", type=float, default=1.0,
                    help="SLO-scale sweep knob (paper §6.4)")
    ap.add_argument("--slo-time-scale", type=float, default=1.0,
                    help="engine steps per abstract SLO second")
    ap.add_argument("--crash", action="append", default=[],
                    metavar="ENGINE:STEP",
                    help="chaos: kill engine ENGINE at step STEP "
                         "(repeatable; DESIGN.md §Fault tolerance)")
    ap.add_argument("--rejoin", action="append", default=[],
                    metavar="ENGINE:STEP",
                    help="chaos: revive a crashed engine at step STEP "
                         "(fresh state; its old residents were already "
                         "re-dispatched)")
    ap.add_argument("--transfer-loss-p", type=float, default=0.0,
                    help="chaos: probability a migration transfer is "
                         "lost on the wire (rolled back after timeout)")
    ap.add_argument("--transfer-stall-p", type=float, default=0.0,
                    help="chaos: probability a transfer stalls past its "
                         "deadline (delivered late, treated as lost)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault injector")
    ap.add_argument("--migration-timeout-steps", type=int, default=4,
                    help="steps before an in-flight transfer is rolled "
                         "back to its sender")
    ap.add_argument("--dead-after-steps", type=int, default=6,
                    help="heartbeat-free steps before an engine is "
                         "declared dead and its residents re-dispatched")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    def _events(specs):
        return tuple((int(e), float(s)) for e, s in
                     (item.split(":", 1) for item in specs))

    faults = None
    if args.crash or args.rejoin or args.transfer_loss_p > 0 \
            or args.transfer_stall_p > 0:
        from repro.control.faults import FaultSpec
        faults = FaultSpec(seed=args.fault_seed,
                           crashes=_events(args.crash),
                           rejoins=_events(args.rejoin),
                           transfer_loss_p=args.transfer_loss_p,
                           transfer_stall_p=args.transfer_stall_p)

    tp = ([int(x) for x in args.tp.split(",")] if "," in args.tp
          else int(args.tp))
    tps = tp if isinstance(tp, list) else [tp] * args.engines
    if any(t > 1 for t in tps):
        assert not args.host_loop, "--tp > 1 needs the device-resident loop"
        need = max(tps)
        assert len(jax.devices()) >= need, (
            f"--tp {args.tp} needs {need} devices, have "
            f"{len(jax.devices())} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} for CPU)")

    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    srv = build_server(
        cfg,
        ServerConfig(policy=args.policy, refinement=args.refinement,
                     balancing=args.balancing, seed=args.seed,
                     preemption=args.preemption, slo_scale=args.slo_scale,
                     slo_time_scale=args.slo_time_scale, faults=faults,
                     migration_timeout_steps=args.migration_timeout_steps,
                     dead_after_steps=args.dead_after_steps,
                     host_kv_budget=(args.host_kv_budget
                                     if args.prefix_cache else 0)),
        engines=args.engines, tp=tp, max_slots=args.max_slots,
        max_seq=args.max_seq, attn_backend=args.attn_backend,
        kv_dtype=args.kv_dtype,
        device_resident=False if args.host_loop else None,
        prefill_token_budget=args.prefill_budget,
        chunked_prefill=False if args.no_chunked_prefill else None,
        prefix_cache=args.prefix_cache)
    # the same ShareGPT-shaped trace the simulator runs, arrival times
    # mapped to server steps, lengths capped to the reduced model
    spec = WorkloadSpec(rate=args.arrival_rate,
                        duration=args.requests / args.arrival_rate,
                        seed=args.seed)
    trace = generate(spec)[:args.requests]
    if args.slo_class_mix:
        mix = parse_class_mix(args.slo_class_mix)
        classes = assign_classes(len(trace),
                                 mix, np.random.default_rng(args.seed))
        trace = [dataclasses.replace(r, slo_class=c)
                 for r, c in zip(trace, classes)]
    for req, step in requests_from_trace(trace, vocab_size=cfg.vocab_size,
                                         max_seq=args.max_seq,
                                         seed=args.seed):
        srv.submit_at(req, step)
    srv.run(max_steps=100 * args.requests)
    print("summary:", {k: round(v, 2) if isinstance(v, float) else v
                       for k, v in srv.summary().items()})
    print("stage bounds:", srv.stage_bounds)


if __name__ == "__main__":
    main()
