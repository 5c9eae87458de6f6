"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A run builds the cell's weights on the device from the seed (one jitted
program), wraps the program's network with them in the server's guided
``Denoiser``, warms the buckets its traffic uses, then drives
``ServeEngine.submit`` and ``ServeEngine.step`` from its own loop for
``--seconds``: an open loop that submits each request when it is due, or
a backlog kept a fixed depth. Once the window has closed it reads the
device's peak memory, frees the server, recomputes a sample of the
served requests with the plain reference (``reference.py``) and
compares, against ``limits/<cell>.json``: the median and the widest over
the sample of each answer's relative L2 gap, and the share of the served
values that are exact bfloat16 numbers (the configurations state a
float32 solver state). The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each compared number beside its limit. A run that finds no TPU, or fewer
chips than the cell asks for, prints no result and exits 2.

Everything a cell needs is found by name (``resolve``): ``BENCHMARK.json``
names the cell's configuration file, its traffic (``traffic/<name>.json``),
its per-layer metrics (``metrics/<name>.py``, each a ``read(run)`` that
returns a number or None) and this directory's ``limits/<cell>.json``.
The configuration file names its denoiser family under ``family``
(``families/<family>.py``: the weight tree, the program under test, the
requests' conditioning, the reference backbone and the FLOP counts) and
its noise schedule under ``schedule.kind`` (``schedules/<kind>.py``, which
the reference's SA-Solver tables are built on). A configuration without
``family``, or one that names a family or schedule that is not there,
stops ``resolve`` with the path it looked for. So a second architecture
is a family file, perhaps a schedule file, a configuration, a traffic
file, a limits file and ``BENCHMARK.json`` entries: no existing file
changes.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import modules  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: how long past the window the open loop waits for requests due in it
WAIT_S = 60.0
#: first rid of the warm-up requests (the window's count up from 0)
WARM_RID = 1 << 30


# ------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    data_dir: str
    family: object       # the configuration's families/<family>.py


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, bench_path: str | None = None,
            data_dir: str = HERE) -> Cell:
    """The cell named ``workload`` and every file it names."""
    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench = _load(bench_path)
    root = os.path.dirname(os.path.abspath(bench_path))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {bench_path}; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    conf_path = os.path.join(
        root, {c["name"]: c for c in bench["configs"]}[w["config"]]["file"])
    config = _load(conf_path)
    if "family" not in config:
        raise SystemExit(f"{conf_path} names no family: give it "
                         f"\"family\": <name>, the module "
                         f"{os.path.join(data_dir, 'families', '<name>.py')}")
    family = modules.load(data_dir, "families", config["family"])
    modules.load(data_dir, "schedules", config["schedule"]["kind"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_load(os.path.join(data_dir, "traffic",
                                   w["traffic"] + ".json")),
        limits=_load(os.path.join(data_dir, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
        data_dir=data_dir, family=family)


def reader(data_dir: str, name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    return modules.load(data_dir, "metrics", name).read


# ------------------------------------------------------------ counters
class CompileCounter:
    """Counts XLA backend compiles (a process-wide ``jax.monitoring``
    listener, registered once)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax
            inst = super().__new__(cls)
            inst.n = 0

            def listen(name, *_, **__):
                if name == cls.EVENT:
                    inst.n += 1
            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._instance = inst
        return cls._instance


# ------------------------------------------------------------ the server
@dataclasses.dataclass
class Server:
    engine: object
    den: object
    spec: object         # the SamplerSpec every request of the cell asks for
    scale: float         # its guidance scale
    shape: tuple
    scheduler: str
    buckets: tuple
    #: the compiled executables warm() loaded, whose metadata names each
    #: op's scope for the trace reduction
    executables: list = dataclasses.field(default_factory=list)


def build_server(cell: Cell, params, seed: int,
                 precision: str | None = None) -> Server:
    """The server under test: the family's program network in a guided
    ``Denoiser``. ``precision`` overrides the solver's default precision
    policy (only the control does)."""
    from repro.core import Denoiser
    from repro.core.samplers import SamplerSpec

    conf, tr = cell.config, cell.traffic
    network, schedule, null_cond = cell.family.program(conf)
    pred = conf["program"]["prediction"]
    den = Denoiser(network, schedule, prediction=pred, guidance=True,
                   params=params, null_cond=null_cond)
    c = tr["request"]
    spec = SamplerSpec.from_nfe(
        c["sampler"], c["nfe"], schedule=schedule,
        predictor_order=c["predictor_order"],
        corrector_order=c["corrector_order"], tau=c["tau"],
        prediction=pred, guidance=True)
    if precision is not None:
        spec = dataclasses.replace(spec, precision=precision)
    srv = tr["server"]
    noise_seed, solve_seed = engine_seeds(seed)
    kw = dict(model_key=("bench", conf["name"]), noise_seed=noise_seed,
              solve_seed=solve_seed)
    from repro.serve import ServeEngine
    if srv["scheduler"] == "step":
        engine = ServeEngine(den, scheduler="step", lanes=srv["lanes"], **kw)
        buckets = (int(srv["lanes"]),)
    else:
        buckets = tuple(int(b) for b in srv["bucket_sizes"])
        engine = ServeEngine(den, bucket_sizes=buckets, **kw)
    shape = (conf["model"]["latent_tokens"], conf["model"]["latent_dim"])
    return Server(engine, den, spec, float(c["guidance_scale"]), shape,
                  srv["scheduler"], buckets)


def engine_seeds(seed: int) -> tuple[int, int]:
    """The server's noise and solve seeds, drawn from the run's seed."""
    from . import traffic
    a, b = traffic.rng(seed, "engine").integers(0, 2 ** 31 - 1, 2)
    return int(a), int(b)


def submit(server: Server, req) -> None:
    server.engine.submit(server.spec, server.shape, rid=req.rid,
                         cond=req.cond, guidance_scale=server.scale)


def has_work(server: Server) -> bool:
    eng = server.engine
    if server.scheduler == "step":
        h = eng.health()
        return h["pending"] > 0 or h["active"] > 0
    return eng.pending() > 0


def drain(server: Server) -> list:
    out = []
    while has_work(server):
        out.extend(server.engine.step())
    return out


def warm(server: Server, cell: Cell) -> dict:
    """Compile (or load from the cache) and run once every bucket the
    cell's traffic uses; returns the seconds of each part."""
    import jax
    import numpy as np
    from repro.core.samplers import build_plan, warmup

    from .traffic import Request
    proto = cell.family.cond_proto(cell.config["model"])
    cond = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), proto)
    split = {}
    rid = WARM_RID
    if server.scheduler == "step":
        # two running batches, so a join, a merge and a migration all run
        t = time.perf_counter()
        for _ in range(server.buckets[0] + 2):
            submit(server, Request(rid, 0.0, cond))
            rid += 1
        drain(server)
        split[f"lanes{server.buckets[0]}_compile_and_run_s"] = \
            time.perf_counter() - t
        # the compiled step, join and copy programs, for their op names
        from repro.core.samplers import stepwise
        server.executables.extend(
            aot for fns in list(stepwise._STEP_CACHE.values())
            for aot in (getattr(fns, a, None) for a in
                        ("_aot_step", "_aot_join", "_aot_copy"))
            if aot is not None)
        return split
    for b in server.buckets:
        t = time.perf_counter()
        server.executables.append(warmup(
            build_plan(server.spec), server.den, server.shape, batch=b,
            cond=proto, model_key=server.engine.model_key))
        t1 = time.perf_counter()
        for _ in range(b):
            submit(server, Request(rid, 0.0, cond))
            rid += 1
        drain(server)
        split[f"bucket{b}"] = {"compile_or_load_s": t1 - t,
                               "run_s": time.perf_counter() - t1}
    return split


def bucket_totals(engine) -> dict:
    tot = {"lane_steps": 0, "active_lane_steps": 0}
    for b in engine.stats()["buckets"].values():
        for k in tot:
            tot[k] += b[k]
    return tot


# ------------------------------------------------------------- the loops
@dataclasses.dataclass
class Record:
    rid: int
    due: float                  # seconds after the window opened
    cond: object = None
    submit: float | None = None
    done: float | None = None
    status: str = "pending"
    x0: object = None


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def open_loop(server: Server, reqs, seconds: float, now, steps: list):
    """Submit each request when due, serve until every one has an
    answer (or WAIT_S past the window). Returns (records, demand
    intervals in which work was pending or running); appends each
    ``step()`` call's (start, seconds) to ``steps``."""
    recs = {r.rid: Record(r.rid, r.due, r.cond) for r in reqs}
    t0 = now()
    deadline = seconds + WAIT_S
    i, n = 0, len(reqs)
    demand, since = [], None
    while True:
        t = now() - t0
        if i < n and reqs[i].due <= t:
            with _span("intake"):
                while i < n and reqs[i].due <= now() - t0:
                    submit(server, reqs[i])
                    recs[reqs[i].rid].submit = now() - t0
                    if since is None:
                        since = recs[reqs[i].rid].submit
                    i += 1
        if has_work(server):
            ts = now()
            with _span("step"):
                res = server.engine.step()
            t = now() - t0
            steps.append((ts - t0, t + t0 - ts))
            with _span("results"):
                for r in res:
                    rec = recs[r.rid]
                    rec.done, rec.status, rec.x0 = t, r.status, r.x0
            if not has_work(server):
                demand.append((since, t))
                since = None
        elif i < n:
            with _span("wait"):
                time.sleep(max(0.0, reqs[i].due - (now() - t0)))
        else:
            break
        if now() - t0 > deadline:
            break
    if since is not None:
        demand.append((since, now() - t0))
    return list(recs.values()), demand


def backlog_loop(server: Server, gen, seconds: float, now, steps: list):
    """Keep ``min_pending`` requests queued; serve until the first
    completion at or after ``seconds`` (or WAIT_S past it). Returns
    (records answered, completion events as (seconds, samples));
    appends each ``step()`` call's (start, seconds) to ``steps``."""
    recs, events = {}, []
    t0 = now()
    while True:
        with _span("intake"):
            short = gen.min_pending - server.engine.pending()
            for r in gen.take(max(0, short), now() - t0):
                submit(server, r)
                recs[r.rid] = Record(r.rid, r.due, r.cond, submit=r.due)
        ts = now()
        with _span("step"):
            res = server.engine.step()
        t = now() - t0
        steps.append((ts - t0, t + t0 - ts))
        with _span("results"):
            for r in res:
                rec = recs[r.rid]
                rec.done, rec.status, rec.x0 = t, r.status, r.x0
        if res:
            events.append((t, sum(r.status == "ok" for r in res)))
            if t >= seconds:
                break
        if t > seconds + WAIT_S:
            break
    return [r for r in recs.values() if r.done is not None], events


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What a run measured, for the metric readers."""
    cell: Cell
    seconds: float
    records: list
    demand: list
    events: list
    setup_s: float
    window_compiles: int
    lane_steps: int
    active_lane_steps: int
    forwards: int               # backbone forwards the device executed
    forward_flops: int          # FLOPs of one forward
    sample_flops: int           # model FLOPs of one sample
    solver_bytes: int           # least bytes of one lane-step's update
    peaks: dict
    trace: dict | None


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, cache: bool = True,
            control: bool = False,
            precision: str | None = None) -> tuple[dict, Run]:
    """One run of ``cell``: the result object (see the module docstring)
    and what the run measured. ``control`` also recomputes the sample
    with the fp8 control and gives its numbers under ``control``;
    ``precision`` serves with the solver's precision policy set to it
    (``"bf16"``: the program's own lower-precision path, a control)."""
    import jax
    import numpy as np

    from . import counts, reference, traffic as traffic_mod, weights
    now = time.perf_counter
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    if cache:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    conf, tr, model = cell.config, cell.traffic, cell.config["model"]
    peaks = counts.peaks(devs[0].device_kind) if require_tpu else {
        "bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    split = {"import_s": now() - T_IMPORT}

    t = now()
    params = weights.make(cell.family, model, conf["weight_std"], seed)
    jax.block_until_ready(params)
    split["weights_s"] = now() - t
    server = build_server(cell, params, seed, precision)
    split["buckets"] = warm(server, cell)
    # what set-up made stays; the window's collections scan only its own
    gc.collect()
    gc.freeze()
    before = bucket_totals(server.engine)
    c0 = compiles.n
    setup_s = now() - T_IMPORT
    print("setup " + json.dumps(dict(split, setup_s=setup_s)), flush=True)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    arrivals = tr["arrivals"]
    draw = functools.partial(cell.family.conds, tr, model)
    steps, gcs = [], GcPauses()
    with gcs, _span("window"):
        if arrivals["kind"] == "backlog":
            gen = traffic_mod.Backlog(tr, seed, draw)
            records, events = backlog_loop(server, gen, seconds, now, steps)
            demand = [(0.0, events[-1][0] if events else seconds)]
        else:
            reqs = traffic_mod.open_loop(tr, seed, seconds, draw)
            records, demand = open_loop(server, reqs, seconds, now, steps)
            events = []
    if trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    window_compiles = compiles.n - c0
    after = bucket_totals(server.engine)
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    longest = sorted(steps, key=lambda s: -s[1])[:5]
    print("window " + json.dumps({
        "steps": len(steps), "step_s_total": sum(d for _, d in steps),
        "longest_steps": [[round(a, 4), round(d, 4)] for a, d in longest],
        "gc_pauses": gcs.n, "gc_s_total": gcs.total, "gc_s_max": gcs.max,
        "compiles": window_compiles}), flush=True)

    tokens = model["latent_tokens"]
    fwd = cell.family.forward_flops(model, tokens)
    lane_steps = after["lane_steps"] - before["lane_steps"]
    spec = server.spec
    per_lane_step = 1.0 if server.scheduler == "step" else \
        spec.nfe / spec.n_steps
    run = Run(
        cell=cell, seconds=seconds, records=records, demand=demand,
        events=events, setup_s=setup_s, window_compiles=window_compiles,
        lane_steps=lane_steps,
        active_lane_steps=after["active_lane_steps"]
        - before["active_lane_steps"],
        forwards=int(round(lane_steps * per_lane_step)) * 2,
        forward_flops=fwd,
        sample_flops=counts.sample_flops(fwd, spec.nfe, True),
        solver_bytes=counts.solver_step_bytes(
            tokens, model["latent_dim"],
            max(spec.predictor_order, spec.corrector_order)),
        peaks=peaks, trace=None)
    if trace:
        from . import trace as trace_mod
        op_scopes = trace_mod.scopes(e.as_text() for e in server.executables)
        with open(os.path.join(TRACE_DIR, "scopes.json"), "w") as f:
            json.dump(op_scopes, f)
        run.trace = trace_mod.reduce(trace_mod.find_xplane(TRACE_DIR),
                                     demand=demand, op_scopes=op_scopes)

    # ----------------------------------------------- the check, afterwards
    if spec.n_steps != spec.nfe - 1:
        raise SystemExit("the reference serves PEC solves only")
    ok = [r for r in records if r.status == "ok"]
    k = min(int(cell.limits["sample"]), len(ok))
    pick = sorted(traffic_mod.rng(seed, "check").choice(
        len(ok), size=k, replace=False)) if k else []
    sample = [ok[i] for i in pick]
    served = np.stack([np.asarray(r.x0).astype(np.float32) for r in sample]) \
        if sample else None
    solver = {"n_steps": spec.n_steps, "tau": spec.tau,
              "predictor_order": spec.predictor_order,
              "corrector_order": spec.corrector_order}
    noise_seed, solve_seed = engine_seeds(seed)
    scale = server.scale
    for r in records:
        r.x0 = None
    del server
    gc.collect()
    ref_kw = dict(family=cell.family, data_dir=cell.data_dir,
                  rids=[r.rid for r in sample],
                  conds=[r.cond for r in sample],
                  scales=[scale] * len(sample), noise_seed=noise_seed,
                  solve_seed=solve_seed, tokens=tokens)
    checks, err, ctrl = {}, [], None
    correct = False
    t = now()
    if served is not None:
        ref = reference.sample(params, model, conf["schedule"], solver,
                               **ref_kw)
        err = rel_err(served, ref)
        checks = compare(err, served, cell.limits)
        correct = all(math.isfinite(e) for e in err) and all(
            c["value"] <= c["limit"] for c in checks.values())
        if control:
            ctl = reference.sample(params, model, conf["schedule"], solver,
                                   quant="fp8", **ref_kw)
            ctrl = {name: c["value"] for name, c in
                    compare(rel_err(ctl, ref), ctl, cell.limits).items()}
            ctrl["per_request"] = [float(e) for e in rel_err(ctl, ref)]
    check_s = now() - t

    failed = sum(1 for r in records if r.status != "ok")
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": failed}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = reader(cell.data_dir, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": v, "unit": m["unit"]}
                   for m in cell.end_to_end
                   if (v := end_to_end(m["name"], run)) is not None}
    out["metrics"] = metrics
    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs),
                     "memory_peak_bytes": peak}
    if trace:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    if ctrl is not None:
        out["control"] = ctrl
    out["check_s"] = check_s
    out["sample_rids"] = [r.rid for r in sample]
    out["x0_rel_err"] = [float(e) for e in err]
    out["checks"] = checks
    return out, run


def compare(err, served, limits: dict) -> dict:
    """The compared numbers of a sample, each beside its limit: the
    median and the widest of the answers' relative gaps to the
    reference, and the share of the served values that are exact
    bfloat16 numbers (about 2**-16 for a float32 state, 1 for a state
    carried in bfloat16)."""
    import jax.numpy as jnp
    import numpy as np
    x = np.asarray(served, np.float32)
    exact = float(np.mean(x == x.astype(jnp.bfloat16).astype(np.float32)))
    values = {"x0_rel_err_median": float(np.median(err)),
              "x0_rel_err_max": float(np.max(err)),
              "x0_bf16_share": exact}
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in values.items()}


class GcPauses:
    """Python's garbage-collection pauses while the context is open."""

    def __init__(self):
        self.n, self.total, self.max, self._t = 0, 0.0, 0.0, None

    def _note(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n, self.total, self.max = \
                self.n + 1, self.total + d, max(self.max, d)
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)


def rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float64).reshape(len(got), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    return list(np.linalg.norm(got - want, axis=1)
                / np.linalg.norm(want, axis=1))


def end_to_end(name: str, run: Run):
    """The end-to-end metrics, from the host clock."""
    if name == "setup_s":
        return run.setup_s
    recs = run.records
    if name in ("latency_p50_s", "latency_p95_s"):
        if run.events:
            return None
        end = max([r.done for r in recs if r.done is not None] or [0.0])
        lat = [(r.done if r.status == "ok" else max(end, r.due)) - r.due
               for r in recs]
        from .stats import quantile
        return quantile(lat, 0.5 if name.endswith("p50_s") else 0.95)
    if name == "samples_per_s":
        if len(run.events) < 2:
            return None
        (ta, _), (tb, _) = run.events[0], run.events[-1]
        return sum(n for _, n in run.events[1:]) / (tb - ta)
    raise KeyError(f"no end-to-end metric {name!r}")


class NoChip(RuntimeError):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    # libtpu logs under /tmp/tpu_logs unless told otherwise; a run writes
    # only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = resolve(args.workload)
    try:
        import repro  # noqa: F401  the program under test
    except ImportError as e:
        print(f"bench: cannot import the program from {ROOT}/src: {e}",
              file=sys.stderr)
        return 2
    try:
        out, _ = measure(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}. No chip, no result.", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
