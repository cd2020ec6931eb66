"""One run of one cell: build the server, warm up, serve the cell's
closed-loop clients, measure a window, check the served tokens against the
reference, and assemble the result line.

Everything specific to a cell is found by name: ``configs/<config>.json``
(model sizes and the server's engine options), ``arch/<architecture>.py``
(named by the configuration's ``architecture`` key), ``traffic/<mix>.json``
(the generator's parameters), ``limits/<cell>.json`` (the limits of the
numbers compared) and ``metrics/<metric>.py`` (one reader per per-layer
metric). Adding a cell, a mix or a metric adds files and edits none;
so does adding a configuration, of an architecture the benchmark has or
of a new one, which brings ``arch/<name>.py`` beside its ``configs/``,
``traffic/``, ``limits/`` and ``metrics/`` files.

An architecture module gives:

* ``dims(config) -> m``: the sizes, a dict of plain values (its
  ``"dtype"`` the served dtype's name), from the configuration file;
* ``program_config(name, m, max_position)``: the program's
  ``ModelConfig`` for them;
* ``make_params(m, seed, shardings=None)``: the served weights from the
  seed, on the device in one jitted call, made straight into
  ``shardings`` (a tree of shardings like the params) where given;
* ``Reference(m, seed)``: a plain float32 forward with the weights made
  again from the seed, importing nothing of the program;
  ``.logits(tokens, start, fp8=False)`` gives the float32 logits at
  positions ``start..`` of ``tokens``, and ``fp8=True`` its float8
  control;
* the arithmetic the metric readers call through ``ctx["flops"]``:
  ``token_flops(m, ctx, logits)``, ``chunk_flops(m, ctx0, clen,
  logits)`` and ``attn_least_time(m, decode_ctx, chunks, peak_flops,
  hbm_bw)``, each of the whole model (all its heads and layers).

The chips are the configuration's: ``serve.tp`` chips share each layer,
and a cell's ``chips`` must equal it. The program puts every engine on
the first ``tp`` devices (``launch/mesh.py``), so ``serve.engines``
engines are replicas sharing those chips; engines on chips of their own
are not served yet. The readers get the cell's chip count as
``ctx["chips"]``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

import peaks  # noqa: E402
import traffic  # noqa: E402
import xtrace  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------
def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_bench() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def load_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = _json(HERE / "configs" / f"{cell['config']}.json")
    mix = _json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = _json(HERE / "limits" / f"{name}.json")
    return cell, config, mix, limits


def load_architecture(name: str, owner: str = "?"):
    """The architecture module ``arch/<name>.py`` (see above), loaded
    once per process. ``owner`` names the configuration in the refusal."""
    path = HERE / "arch" / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier()
            and path.is_file()):
        known = sorted(p.stem for p in (HERE / "arch").glob("*.py"))
        raise SystemExit(f"configuration {owner!r}: unknown architecture "
                         f"{name!r}; known: {known}")
    modname = f"arch_{name}"
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[modname] = mod
    return sys.modules[modname]


def architecture(cell: dict, config: dict):
    """The configuration's architecture module, after checking that the
    configuration names one and that the cell asks for the chips the
    configuration uses."""
    owner = config.get("name")
    if "architecture" not in config:
        raise SystemExit(f"configuration {owner!r} names no architecture "
                         f"(its 'architecture' key)")
    srv = config["serve"]
    if "tp" not in srv:
        raise SystemExit(f"configuration {owner!r} gives no serve.tp")
    if cell["chips"] != srv["tp"]:
        raise SystemExit(
            f"workload {cell['name']!r} asks for {cell['chips']} chips; its "
            f"configuration's engines share serve.tp = {srv['tp']}")
    return load_architecture(config["architecture"], owner)


def make_params(arch, m: dict, seed: int, tp: int):
    """The weights, at ``tp`` > 1 made straight into the engine's serving
    shardings over its mesh, so that no device ever holds more than its
    share and the engine's own placement moves nothing."""
    if tp == 1:
        return arch.make_params(m, seed)
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_tp_mesh
    from repro.launch.shardings import serving_param_spec_tree
    mesh = make_tp_mesh(tp)
    specs = serving_param_spec_tree(
        jax.eval_shape(lambda: arch.make_params(m, seed)), tp)
    return arch.make_params(m, seed, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))


def load_metric_readers(bench: dict, cell_name: str) -> Dict[str, Callable]:
    out = {}
    for met in bench["per_layer"]:
        if cell_name not in met.get("workloads", [cell_name]):
            continue
        path = HERE / "metrics" / f"{met['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "metric_" + met["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[met["name"]] = (mod.read, met["unit"])
    return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def check_device(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return peaks.peaks_for(devs[0].device_kind)


def memory_peak_bytes() -> int:
    best = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


# ---------------------------------------------------------------------------
# spans, counters and token stamps, recorded from outside the program
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EngineStep:
    eng: int
    n: int
    t0: float
    t1: float
    decode_ctx: List[int]
    chunks: List[tuple]          # (ctx0, clen, completes_prompt)
    max_slots: int
    kv_used: float               # blocks in use / pool blocks, after


class Recorder:
    def __init__(self):
        self.engine_steps: List[EngineStep] = []
        self.server_steps: List[tuple] = []
        self.stamps: Dict[int, List[float]] = {}
        # programs compiled or loaded from the persistent cache (JAX's
        # compile event fires for both), and the loads among them
        self.compiles: List[float] = []
        self.compile_names: List[str] = []
        self.cache_loads: List[float] = []
        self._n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def reset(self) -> None:
        """Forget the spans and stamps so far (the compile log stays)."""
        self.engine_steps, self.server_steps, self.stamps = [], [], {}

    def _on_event(self, event, duration, fun_name="", **_):
        if event == COMPILE_EVENT:
            self.compiles.append(time.perf_counter())
            self.compile_names.append(str(fun_name))

    def _on_count(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_loads.append(time.perf_counter())

    def on_token(self, req, tok):
        self.stamps.setdefault(req.req_id, []).append(time.perf_counter())

    def attach(self, srv) -> None:
        for eng in srv.engines:
            self._wrap_engine(eng)
        step, submit = srv.step, srv.submit

        def server_step():
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("server.step",
                                                  step_num=len(
                                                      self.server_steps)):
                out = step()
            self.server_steps.append((t0, time.perf_counter()))
            return out

        def server_submit(req):
            with jax.profiler.TraceAnnotation("server.submit"):
                return submit(req)

        srv.step, srv.submit = server_step, server_submit

    def _wrap_engine(self, eng) -> None:
        step = eng.step

        def engine_step(*a, **k):
            live, pre = [], []
            for i, r in enumerate(eng.slots):
                if r is None:
                    continue
                if r.prefilling:
                    pre.append((r, r.ctx_done))
                else:
                    live.append(int(eng.slot_len[i]))
            waiting = list(eng.waiting)
            self._n += 1
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.step", eng=eng.id,
                                              n=self._n):
                out = step(*a, **k)
            t1 = time.perf_counter()
            chunks = []
            for r, c0 in pre + [(r, None) for r in waiting]:
                if c0 is None:
                    if r.engine_id != eng.id or r.ctx_done == 0:
                        continue      # still queued
                    c0 = r.cached_tokens
                clen = r.ctx_done - c0
                if clen > 0:
                    chunks.append((c0, clen, r.ctx_done >= len(r.prompt)))
            alloc = eng.allocator
            self.engine_steps.append(EngineStep(
                eng.id, self._n, t0, t1, live, chunks, eng.max_slots,
                alloc.allocated_blocks / eng.num_blocks))
            return out

        eng.step = engine_step


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
# the warm-up serves this many windows' worth of steps past the lead-in
COVER = 1.5


def _programs(eng) -> dict:
    """The engine's compiled-program caches: its jitted functions and the
    dicts of them it fills per step shape."""
    return {k: v for k, v in vars(eng).items()
            if k.endswith("_fns") or (callable(v) and hasattr(v, "lower")
                                      and hasattr(v, "trace"))}


def build_server(config: dict, params, model, rec: Recorder,
                 programs: Optional[List[dict]] = None):
    """The cell's server, from ``launch/serve.build_server``. Engine i
    takes ``programs[i]`` (another server's engine i's compiled-program
    caches, see ``_programs``), so a second server of the same shapes
    compiles nothing."""
    from repro.launch.serve import build_server as _build
    from repro.serving.engine import Engine
    from repro.serving.server import ServerConfig

    srv_opts = config["serve"]
    # the router's tie-breaks are the deployment's, not the run's: one
    # fixed seed, so every run routes its requests alike
    sc = ServerConfig(policy=srv_opts["policy"], seed=0)

    # MILSServer's own factory ties the pool to max_slots x max_seq; the
    # configuration sizes the pool apart from the slot count, so the
    # engines are built with the same options plus ``token_budget``
    def engine(i):
        e = Engine(i, model, params, max_slots=srv_opts["max_slots"],
                   max_seq=srv_opts["max_seq"],
                   token_budget=srv_opts["token_budget"],
                   block_size=srv_opts["block_size"],
                   kv_dtype=sc.kv_dtype, host_kv_budget=sc.host_kv_budget,
                   preemption=sc.preemption,
                   slo_time_scale=sc.slo_time_scale, tp=srv_opts["tp"])
        if programs is not None:
            vars(e).update(programs[i])
        return e

    srv = _build(model.cfg, sc, engines=srv_opts["engines"],
                 tp=srv_opts["tp"],
                 max_seq=srv_opts["max_seq"],
                 max_slots=srv_opts["max_slots"], params=params,
                 engine_factory=engine, on_token=rec.on_token)
    rec.attach(srv)
    return srv


class Clients:
    """A closed loop of ``concurrency`` clients, each sending the
    backlog's next request once its last one has finished. Requests are
    sent between server steps, as the finishes fall, never by the clock:
    the server's step sequence depends on the request sizes alone."""

    def __init__(self, srv, backlog, concurrency: int):
        self.srv, self.backlog, self.n = srv, backlog, int(concurrency)
        self.i = 0

    def step(self) -> None:
        from repro.serving.request import ServeRequest
        srv = self.srv
        while (srv.submitted - len(srv.finished) < self.n
               and self.i < len(self.backlog)):
            q = self.backlog[self.i]
            srv.submit(ServeRequest(q.idx, q.prompt, q.output_len))
            self.i += 1
        srv.step()

    def busy(self) -> bool:
        return self.i < len(self.backlog) or _outstanding(self.srv)


def _outstanding(srv) -> bool:
    return len(srv.finished) < srv.submitted


def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def _warm_up(clients: Clients, lead_steps: int, seconds: float,
             rec: Recorder, log=print) -> None:
    """Serve the backlog's sizes (with the warm-up's token ids) on a
    server of its own, for the lead-in and then until its steps past the
    lead-in, at the median length of those that compiled nothing, would
    fill ``COVER`` windows. The server's step sequence depends on the
    request sizes alone, so the measured server then meets the same step
    shapes in the same order: every one of them compiled here, or loaded
    from the persistent cache after the first run in a checkout."""
    n0, h0 = len(rec.compiles), len(rec.cache_loads)
    t0 = time.perf_counter()
    steps, clean = 0, []
    while clients.busy():
        c0 = len(rec.compiles)
        t = time.perf_counter()
        clients.step()
        dt = time.perf_counter() - t
        steps += 1
        if steps <= lead_steps:
            continue
        if len(rec.compiles) == c0:
            clean.append(dt)
        if clean and (steps - lead_steps) * float(np.median(clean)) \
                >= COVER * seconds:
            break
    else:
        log("warm-up: the backlog ran out before the warm-up covered the "
            "window", file=sys.stderr)
    loaded = len(rec.cache_loads) - h0
    log(f"warm-up: {steps} server steps, {len(rec.compiles) - n0 - loaded} "
        f"programs compiled, {loaded} loaded from the persistent cache, "
        f"{time.perf_counter() - t0:.2f} s", file=sys.stderr)


# past the close the measured server serves on, untimed, for at most this
# long, until the check's sample can be drawn
DRAIN_S = 150.0


def _handed_over(r) -> bool:
    return len(r.tokens_by_engine) > 1


def _complete(r) -> bool:
    return (not r.rejected and not r.failed
            and len(r.generated) == r.max_new_tokens)


def _sample_ready(finished, mix, limits, seed) -> bool:
    sample = _sample(finished, mix, seed)
    return (sum(len(r.generated) for r in sample)
            >= limits["min_sampled_tokens"]
            and sum(1 for r in sample if _handed_over(r))
            >= limits.get("min_handed_over", 0))


def _drain(load: Clients, mix: dict, limits: dict, seed: int,
           log=print) -> None:
    """Serve on past the close (the same loop; nothing in it is timed or
    counted in a metric) until the requests finished so far give the
    check its sample: enough served tokens and, where the cell asks for
    one, a request handed over between engines. Long requests, and those
    that cross the stage boundary late in their decode, seldom finish
    inside a short window."""
    t0, steps = time.perf_counter(), 0
    while (load.busy() and time.perf_counter() - t0 < DRAIN_S
           and not _sample_ready(load.srv.finished, mix, limits, seed)):
        load.step()
        steps += 1
    log(f"drain: {steps} server steps past the close, "
        f"{time.perf_counter() - t0:.2f} s, {len(load.srv.finished)} "
        f"finished", file=sys.stderr)


def _free(srv) -> None:
    """Drop a server's device buffers (its pools) now."""
    for e in srv.engines:
        e.cache = None
    srv.engines = []


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True,
        bench: Optional[dict] = None, files: Optional[tuple] = None,
        control: bool = False, log=print) -> dict:
    """One run; returns the result dict (``correct``, ``metrics``, ...).
    With ``control`` the reference's float8 control is put in the
    program's place in the comparison (a run for setting the limit)."""
    from repro.models import build_model

    bench = bench or load_bench()
    cell, config, mix, limits = files or load_cell(bench, cell_name)
    if require_tpu:
        pk = check_device(cell["chips"])
        from repro.launch.serve import enable_compile_cache
        enable_compile_cache()
        # every program, small ones too, goes to the persistent cache, so
        # that a later run finds the shapes an earlier one met
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        pk = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    dev = jax.devices()[0]
    arch = architecture(cell, config)
    m = arch.dims(config)
    rec = Recorder()
    lead = int(mix["lead_in_steps"])

    params = make_params(arch, m, seed, config["serve"]["tp"])
    model = build_model(arch.program_config(config["name"], m,
                                            config["serve"]["max_seq"]))
    clients = int(mix["concurrency"])
    warm = build_server(config, params, model, rec)
    _warm_up(Clients(warm, traffic.build(mix, mix["warmup_seed"],
                                         m["vocab_size"]), clients),
             lead, seconds, rec, log)
    programs = [_programs(e) for e in warm.engines]
    _free(warm)
    del warm
    gc.collect()
    rec.reset()

    srv = build_server(config, params, model, rec, programs)
    load = Clients(srv, traffic.build(mix, seed, m["vocab_size"]), clients)
    for _ in range(lead):
        load.step()
    t_open = time.perf_counter()
    log("window opens: decode rows " + ", ".join(
        f"{sum(1 for r in e.slots if r is not None and not r.prefilling)}"
        f"/{e.max_slots}" for e in srv.engines) + f", {srv.migrations} "
        f"handovers, {len(srv.finished)} finished", file=sys.stderr)
    setup_s = t_open - t_process
    mig0, fin0 = srv.migrations, len(srv.finished)
    n_compiles0 = len(rec.compiles)
    trace_dir = None

    def serve_until(t_stop):
        while load.busy() and time.perf_counter() < t_stop:
            load.step()

    if trace:
        # a few seconds at the end of the window; the file is written
        # after it closes
        serve_until(t_open + seconds - min(4.0, seconds / 2))
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    # no step starts once the time is up; the window closes when the last
    # one started has ended (each ends in its blocking device-to-host
    # copy), so the window holds whole steps: all their tokens, all their
    # time
    serve_until(t_open + seconds)
    t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    if not load.busy():
        log("window: the backlog ran out before the window closed",
            file=sys.stderr)
    window_compiles = len(rec.compiles) - n_compiles0
    window_loads = sum(1 for t in rec.cache_loads if t_open <= t <= t_close)
    names = [n for t, n in zip(rec.compiles, rec.compile_names)
             if t_open <= t <= t_close]
    fin_close = len(srv.finished)
    handovers = srv.migrations - mig0
    _drain(load, mix, limits, seed, log)
    finished = srv.finished[:]

    # ---- end-to-end numbers (client side, host clock) ----
    tokens = sum(1 for st in rec.stamps.values() for t in st
                 if t_open <= t <= t_close)
    done = [r for r in finished if not r.rejected and not r.failed]
    failed = len(finished) - len(done)
    est = [(s.t1 - s.t0) * 1e3 for s in rec.engine_steps
           if t_open <= s.t0 < t_close]
    if est:
        log(f"window: {len(est)} engine steps, p50 {_pct(est, 50):.2f} ms, "
            f"p90 {_pct(est, 90):.2f} ms, max {max(est):.2f} ms",
            file=sys.stderr)
    if names:
        log(f"window: programs compiled or loaded: {sorted(names)}",
            file=sys.stderr)
    log(f"window: {tokens} tokens in {t_close - t_open!r} s, "
        f"{fin_close - fin0} requests finished, {handovers} handovers, "
        f"{window_compiles} programs compiled or loaded ({window_loads} "
        f"loaded)", file=sys.stderr)
    e2e = {"setup_s": setup_s, "tokens_per_s": tokens / (t_close - t_open)}

    # ---- per-layer numbers ----
    ctx = {
        "m": m, "peaks": pk, "seconds": t_close - t_open, "t_open": t_open,
        "t_close": t_close, "rec": rec, "window_compiles": window_compiles,
        "handovers": handovers, "finished_in_window": fin_close - fin0,
        "trace": None, "flops": arch, "chips": cell["chips"],
    }
    breakdown = None
    if trace:
        path = xtrace.find_xplane(trace_dir)
        tr = xtrace.load(path)
        spans = [s for s in tr.spans if s[0] == "server.step"]
        if not tr.chips:
            log(f"trace: no device plane among {tr.planes}", file=sys.stderr)
        if tr.chips and spans:
            w = (min(s[1] for s in spans), max(s[2] for s in spans))
            red = xtrace.reduce(tr, w)
            ctx["trace"] = tr
            ctx["trace_reduced"] = red
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            log(f"trace: {len(tr.ops)} device ops, {len(spans)} server "
                f"steps, busy {red['busy_s']:.4f} s of {red['window_s']:.4f}"
                f" s", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)

    mem = memory_peak_bytes()
    # ---- correctness: the served tokens against the reference ----
    sample = _sample(done, mix, seed)
    log("sample: (prompt, output) tokens " + ", ".join(
        f"({len(r.prompt)}, {len(r.generated)})" for r in sample)
        + f"; {sum(1 for r in sample if _handed_over(r))} handed over",
        file=sys.stderr)
    # free the program's state before the reference runs
    _free(srv)
    del srv, load, params, programs, model
    gc.collect()
    t_ref = time.perf_counter()
    ref = arch.Reference(m, seed)
    gap, ctl_gap, n_tok = [], [], 0
    for r in sample:
        gen = np.asarray(r.generated, np.int32)
        toks = np.concatenate([np.asarray(r.prompt, np.int32), gen[:-1]])
        lg = ref.logits(toks, len(r.prompt) - 1)
        best = lg.max(-1)
        rows = np.arange(len(gen))
        gap.append(best - lg[rows, gen])
        n_tok += len(gen)
        if control:
            pick = ref.logits(toks, len(r.prompt) - 1, fp8=True).argmax(-1)
            ctl_gap.append(best - lg[rows, pick])
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t_ref:.2f} s", file=sys.stderr)
    readings = {"program": gap}
    if control:
        readings["control"] = ctl_gap
    for who, g in readings.items():
        g = np.concatenate(g) if g else np.zeros(1)
        log(f"reading: {who} logit gap max {float(g.max())!r} mean "
            f"{float(g.mean())!r}, tokens off the reference's best "
            f"{int((g > 0).sum())} of {g.size}", file=sys.stderr)
    # the control, where asked for, stands in the program's place
    served = ctl_gap if control else gap
    worst = float(max((float(g.max()) for g in served), default=0.0))
    migrated = sum(1 for r in sample if _handed_over(r))
    compared = {
        "logit_gap": {"value": worst, "limit": limits["logit_gap"]},
        "sampled_tokens": {"value": n_tok,
                           "limit": limits["min_sampled_tokens"]},
        "failed": {"value": failed, "limit": 0},
    }
    if "min_handed_over" in limits:
        compared["handed_over_in_sample"] = {
            "value": migrated, "limit": limits["min_handed_over"]}
    correct = (worst <= limits["logit_gap"]
               and n_tok >= limits["min_sampled_tokens"] and failed == 0
               and migrated >= limits.get("min_handed_over", 0))

    metrics = {}
    if trace:
        for name, (read, unit) in load_metric_readers(
                bench, cell_name).items():
            v = read(ctx)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": unit}
    else:
        for met in bench["end_to_end"]:
            if cell_name in met.get("workloads", [cell_name]):
                metrics[met["name"]] = {"value": float(e2e[met["name"]]),
                                        "unit": met["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if ctx.get("trace_reduced"):
        device["busy_s"] = ctx["trace_reduced"]["busy_s"]
        device["window_s"] = ctx["trace_reduced"]["window_s"]
    out = {"correct": bool(correct), "attempted": len(finished),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def _sample(finished, mix, seed):
    """Requests to compare, among those the measured server finished (by
    the window's close, or in the drain past it): the longest, one handed
    over between engines where any was, then others drawn from the seed
    until the sample holds ``sample_requests``."""
    done = [r for r in finished if _complete(r)]
    if not done:
        return []
    rng = np.random.default_rng(seed)
    pick = [max(done, key=lambda r: (r.length, r.req_id))]
    moved = [r for r in done if _handed_over(r) and r is not pick[0]]
    if moved:
        pick.append(moved[int(rng.integers(len(moved)))])
    rest = [r for r in done if all(r is not p for p in pick)]
    want = int(mix["sample_requests"])
    for i in rng.permutation(len(rest))[:max(0, want - len(pick))]:
        pick.append(rest[int(i)])
    return pick


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for k, v in result["compared"].items():
        print(f"compared: {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
