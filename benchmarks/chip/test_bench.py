"""Tests of the chip benchmark's own parts, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

The repository's tier-1 run collects only ``tests/``; these guard the
yardstick: the counts against hand counts, the reference against the
program, the traffic generator, the trace reduction on a trace recorded
on a TPU v5e, and the check, which must come out false when the timed
path is broken underneath (``faults.py``), when the fp8 control stands
in, and when the program's own bfloat16 solver path serves; and the
family and schedule lookup, against readings frozen from the commit
before it and with a second family and schedule added as files.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import (counts, faults, modules, reference, run,
                              traffic, weights)

sys.path.insert(0, os.path.join(run.ROOT, "src"))

TESTDATA = os.path.join(run.HERE, "testdata")
TINY_BENCH = os.path.join(TESTDATA, "BENCHMARK.json")
CELL = "xl2-256.solve.poisson"
CELLS = [CELL, "xl2-512.solve.backlog", "xl2-256.step.poisson"]


DIT = modules.load(run.HERE, "families", "dit")


def _conf(name):
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the counts
def test_forward_flops_match_hand_counts():
    assert DIT.forward_flops(_conf("dit-xl-2-256")["model"], 256) \
        == pytest.approx(237.2e9, rel=5e-4)
    assert DIT.forward_flops(_conf("dit-xl-2-512")["model"], 1024) \
        == pytest.approx(1.049e12, rel=5e-4)
    assert DIT.attention_flops(_conf("dit-xl-2-512")["model"], 1024) \
        == pytest.approx(135.3e9, rel=5e-4)
    m = _conf("dit-xl-2-256")["model"]
    assert counts.sample_flops(DIT.forward_flops(m, 256), 20, True) == \
        40 * DIT.forward_flops(m, 256)


def test_solver_step_bytes():
    # state, noise, 3 past and the new evaluation read; two states written
    assert counts.solver_step_bytes(256, 16, 3) == 8 * 256 * 16 * 4


def test_peaks_by_device_kind():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


# -------------------------------------------------------- the traffic
def _draw(tr, model=None):
    return functools.partial(DIT.conds, tr, model or {"latent_dim": 16})


def test_seeds_change_the_contents_not_the_schedule():
    tr = _load_traffic("solve.poisson.256px")
    a = traffic.open_loop(tr, 1, 30.0, _draw(tr))
    b = traffic.open_loop(tr, 3_000_000_007, 30.0, _draw(tr))
    assert len(a) == round(tr["arrivals"]["rate_per_s"] * 30)
    assert [r.due for r in a] == [r.due for r in b]
    assert a[-1].due <= 30.0
    assert not np.allclose(a[5].cond, b[5].cond)
    again = traffic.open_loop(tr, 1, 30.0, _draw(tr))
    np.testing.assert_array_equal(again[5].cond, a[5].cond)
    # the gaps are the exponential's quantiles, in the file's order
    gaps = np.diff([0.0] + [r.due for r in a])
    other = dict(tr, arrivals=dict(tr["arrivals"], order_seed=7))
    gaps7 = np.diff([0.0] + [r.due for r in
                             traffic.open_loop(other, 1, 30.0,
                                               _draw(other))])
    np.testing.assert_allclose(np.sort(gaps), np.sort(gaps7))
    assert not np.allclose(gaps, gaps7)


def _load_traffic(name):
    with open(os.path.join(run.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------- the reference
def test_reference_tables_match_the_program():
    from repro.core.samplers import SamplerSpec, build_plan
    tr = _load_traffic("solve.poisson.256px")["request"]
    spec = SamplerSpec.from_nfe(
        "sa", tr["nfe"], schedule="vp_linear",
        predictor_order=tr["predictor_order"],
        corrector_order=tr["corrector_order"], tau=tr["tau"],
        prediction="eps", guidance=True)
    tables = build_plan(spec).host["tables"]
    ref = reference.sa_tables(_conf("dit-xl-2-256")["schedule"], {
        "n_steps": spec.n_steps, "tau": tr["tau"],
        "predictor_order": tr["predictor_order"],
        "corrector_order": tr["corrector_order"]})
    np.testing.assert_allclose(ref["ts"], tables.ts, rtol=1e-12)
    for k in ("decay", "noise", "corr_new"):
        np.testing.assert_allclose(ref[k], getattr(tables, k), rtol=1e-9)
    for k in ("pred", "corr"):
        np.testing.assert_allclose(ref[k], getattr(tables, k)[:, :3],
                                   rtol=1e-9, atol=1e-12)


# ------------------------------------------------------------ the check
def _tiny(workload):
    return run.resolve(workload, bench_path=TINY_BENCH)


@pytest.fixture(autouse=True)
def fresh_executors():
    """Executors are cached by model key: a fault planted in one test
    must not outlive it, nor find a sound executor compiled before it."""
    from repro.core.samplers import clear_compile_cache
    from repro.core.samplers.stepwise import clear_stepwise_cache
    clear_compile_cache()
    clear_stepwise_cache()
    yield
    clear_compile_cache()
    clear_stepwise_cache()


@pytest.fixture
def f32_program(monkeypatch):
    """The tiny program computing in float32, so that a sound run agrees
    with the reference to float32 rounding and any fault stands out.
    Patches this directory's DiT family; call it with another data
    directory to patch that one's too."""
    import jax.numpy as jnp

    def patch(data_dir=run.HERE):
        fam = modules.load(data_dir, "families", "dit")
        orig = fam.program_config
        monkeypatch.setattr(fam, "program_config", lambda conf: (
            dataclasses.replace(orig(conf), dtype=jnp.float32)))
    patch()
    return patch


def _measure(cell, seed=5, seconds=1.5, **kw):
    out, measured = run.measure(cell, seed, seconds, False,
                                require_tpu=False, cache=False, **kw)
    assert out["attempted"] > 0 and out["failed"] == 0
    return out, measured


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, f32_program):
    out, _ = _measure(_tiny(workload))
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"x0_rel_err_median", "x0_rel_err_max",
                                  "x0_bf16_share"}
    assert out["checks"]["x0_rel_err_max"]["value"] < 1e-3
    assert out["checks"]["x0_bf16_share"]["value"] < 0.01
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in _tiny(workload).end_to_end}
    assert set(out["metrics"]) == want


@pytest.fixture
def planted(request):
    undo = faults.plant(request.param)
    yield request.param
    undo()


@pytest.mark.parametrize("planted", ["stuck", "shifted"], indirect=True)
@pytest.mark.parametrize("workload", CELLS)
def test_a_fault_in_every_answer_is_caught(workload, planted, f32_program):
    """A step that returns its state unchanged; each answer handed to
    the request after it."""
    out, _ = _measure(_tiny(workload))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("planted", ["lane"], indirect=True)
@pytest.mark.parametrize("workload", ["xl2-256.step.poisson",
                                      "xl2-512.solve.backlog"])
def test_one_lane_of_eight_wrong_is_caught(workload, planted, f32_program):
    """Lane 3 of 8 returns lane 2's answer: a minority of the requests
    is wrong, the median of the gaps does not move, the widest does.
    Every answer is compared here, so the test does not hang on the
    sample's draw."""
    cell = _tiny(workload)
    cell.limits = dict(cell.limits, sample=10 ** 6)
    if cell.traffic["arrivals"]["kind"] == "poisson":
        # arrivals dense enough that all eight lanes fill
        cell.traffic = copy.deepcopy(cell.traffic)
        cell.traffic["arrivals"]["rate_per_s"] = 100.0
    faults.REACHED.clear()
    # a window long enough that the fault reaches several answers: the
    # tiny model's wrong answers read 0.65-2.4, the limit is for DiT-XL
    out, _ = _measure(cell, seconds=1.0)
    reached = sum(r in faults.REACHED for r in out["sample_rids"])
    assert 0 < reached < len(out["sample_rids"]) / 2, faults.REACHED
    checks = out["checks"]
    assert checks["x0_rel_err_median"]["value"] < \
        checks["x0_rel_err_median"]["limit"]
    widest = checks["x0_rel_err_max"]
    assert widest["value"] > widest["limit"]
    assert not out["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_fails_the_limit(workload, f32_program):
    """The reference at float8 where the program keeps bfloat16, put in
    the program's place, reads above one of the cell's limits."""
    cell = _tiny(workload)
    out, _ = run.measure(cell, 5, 1.5, False, require_tpu=False,
                         cache=False, control=True)
    assert out["correct"]
    assert any(out["control"][k] > out["checks"][k]["limit"]
               for k in out["checks"]), out["control"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_programs_bf16_solver_path_is_caught(workload):
    """The configurations state a float32 solver state; the program's
    own ``precision="bf16"`` path, switched on, comes out not correct."""
    out, _ = _measure(_tiny(workload), precision="bf16")
    assert not out["correct"], out["checks"]
    assert out["checks"]["x0_bf16_share"]["value"] == 1.0


def test_a_traffic_mix_is_added_as_a_file(tmp_path, f32_program):
    """A new mix is a data file and a BENCHMARK.json entry: no code."""
    data = tmp_path / "chip"
    for d in ("traffic", "limits", "metrics", "families", "schedules"):
        shutil.copytree(os.path.join(run.HERE, d), data / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    f32_program(str(data))
    mix = _load_traffic("solve.poisson.256px")
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["server"]["bucket_sizes"] = [2, 4]
    (data / "traffic" / "dummy.slow.json").write_text(json.dumps(mix))
    (data / "limits" / "tiny.dummy.json").write_text(
        (data / "limits" / f"{CELL}.json").read_text())
    with open(TINY_BENCH) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.dummy", "config": "dit-tiny",
                               "traffic": "dummy.slow", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.dummy")
    shutil.copy(os.path.join(TESTDATA, "dit-tiny.json"), tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.resolve("tiny.dummy", bench_path=str(tmp_path /
                                                    "BENCHMARK.json"),
                       data_dir=str(data))
    out, measured = _measure(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == round(4.0 * 1.5)
    assert "latency_p95_s" in out["metrics"]
    assert run.reader(cell.data_dir, "lane_occupancy")(measured) <= 100.0


# ------------------------------------------- families and schedules
READINGS = os.path.join(TESTDATA, "dit.readings.json")
ADDITIONS = os.path.join(TESTDATA, "additions")
SOLVER = {"n_steps": 19, "tau": 1.0, "predictor_order": 3,
          "corrector_order": 1}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _readings(path: str) -> dict:
    """What the DiT cells draw and count, as ``dit.readings.json`` holds
    it: digests of the bytes, and the counts as integers."""
    import jax
    with open(path) as f:
        conf = json.load(f)
    fam = modules.load(run.HERE, "families", conf["family"])
    m = conf["model"]
    tok = m["latent_tokens"]
    leaves, _ = weights.specs(fam, m, conf["weight_std"])
    got = {"weight_specs": _digest([np.frombuffer(json.dumps([
        ["/".join(names), list(shape), float(std).hex()]
        for names, shape, std in leaves]).encode(), np.uint8)])}
    got["forward_flops"] = fam.forward_flops(m, tok)
    got["attention_flops"] = fam.attention_flops(m, tok)
    got["sample_flops"] = counts.sample_flops(got["forward_flops"], 20, True)
    got["sa_tables"] = {k: _digest([v]) for k, v in sorted(
        reference.sa_tables(conf["schedule"], SOLVER).items())}
    poisson = _load_traffic("step.poisson.256px")
    backlog = _load_traffic("solve.backlog16")
    for seed in (5, 3_000_000_007):
        reqs = traffic.open_loop(poisson, seed, 5.0,
                                 functools.partial(fam.conds, poisson, m))
        got[f"open_loop_conds.{seed}"] = _digest([r.cond for r in reqs[:4]])
        gen = traffic.Backlog(backlog, seed,
                              functools.partial(fam.conds, backlog, m))
        got[f"backlog_conds.{seed}"] = _digest(
            [r.cond for r in gen.take(16, 0.0) + gen.take(3, 1.0)])
    if conf["name"] != "dit-tiny":
        return got      # weights and the reference's x0 at tiny sizes only
    params = weights.make(fam, m, conf["weight_std"], 5)
    got["weights"] = _digest(jax.tree.leaves(params))
    reqs = traffic.open_loop(poisson, 5, 5.0,
                             functools.partial(fam.conds, poisson, m))
    for quant in ("f32", "fp8"):
        x0 = reference.sample(
            params, m, conf["schedule"], SOLVER, family=fam, rids=[3, 11],
            conds=[reqs[3].cond, reqs[11].cond], scales=[1.5, 1.5],
            noise_seed=1234, solve_seed=98765, tokens=tok,
            quant=None if quant == "f32" else quant)
        got[f"reference_x0.{quant}"] = _digest([x0])
        got[f"reference_x0.{quant}.head"] = [
            float(v).hex() for v in np.asarray(x0).ravel()[:4]]
    return got


@pytest.mark.parametrize("name", ["dit-tiny", "dit-xl-2-256",
                                  "dit-xl-2-512"])
def test_dit_readings_equal_the_parents_bit_for_bit(name):
    """The weights (at the full sizes: each leaf's path, shape and spread,
    in the order they are drawn), the conds of both loops, the SA-Solver
    tables, the FLOP counts and the reference's x0 of two requests equal
    those frozen from the commit before the families moved into their
    own files (XLA:CPU on x86-64 for the x0)."""
    with open(READINGS) as f:
        want = json.load(f)[name]
    path = os.path.join(TESTDATA, "dit-tiny.json") if name == "dit-tiny" \
        else os.path.join(run.HERE, "configs", name + ".json")
    assert _readings(path) == want


def test_the_reference_imports_nothing_of_the_program():
    """A cell resolved, its weights made and two requests recomputed by
    the reference, in a process that could import the program, leave
    ``repro`` out of ``sys.modules``."""
    code = """
import importlib.util, json, sys
from benchmarks.chip import reference, run, weights
cell = run.resolve("xl2-256.step.poisson", bench_path=sys.argv[1])
m = cell.config["model"]
params = weights.make(cell.family, m, cell.config["weight_std"], 5)
reference.sample(params, m, cell.config["schedule"], {
    "n_steps": 3, "tau": 1.0, "predictor_order": 2,
    "corrector_order": 1}, family=cell.family, rids=[0, 1],
    conds=cell.family.conds({"cond_std": 0.5}, m,
                            __import__("numpy").random.default_rng(0), 2),
    scales=[1.5, 1.5], noise_seed=1, solve_seed=2,
    tokens=m["latent_tokens"])
print(json.dumps([importlib.util.find_spec("repro") is not None,
                  sorted(k for k in sys.modules if k.split(".")[0]
                         == "repro")]))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [run.ROOT, os.path.join(run.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", code, TINY_BENCH],
                       cwd=run.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    findable, imported = json.loads(p.stdout.splitlines()[-1])
    assert findable and imported == []


@pytest.mark.parametrize("edit, looked_for", [
    ("drop family", os.path.join("families", "<name>.py")),
    ("family mmdit", os.path.join("families", "mmdit.py")),
    ("schedule kind rectified_flow",
     os.path.join("schedules", "rectified_flow.py")),
])
def test_resolve_names_the_file_it_looked_for(tmp_path, edit, looked_for):
    """A configuration without ``family``, or naming a family or a
    schedule kind that has no file, stops ``resolve`` with that path."""
    with open(os.path.join(TESTDATA, "dit-tiny.json")) as f:
        conf = json.load(f)
    if edit == "drop family":
        del conf["family"]
    elif edit == "family mmdit":
        conf["family"] = "mmdit"
    else:
        conf["schedule"]["kind"] = "rectified_flow"
    (tmp_path / "dit-tiny.json").write_text(json.dumps(conf))
    shutil.copy(TINY_BENCH, tmp_path / "BENCHMARK.json")
    with pytest.raises(SystemExit) as e:
        run.resolve(CELL, bench_path=str(tmp_path / "BENCHMARK.json"))
    assert os.path.join(run.HERE, looked_for) in str(e.value)


def _file_digests(root) -> dict:
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = \
                    hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.mark.parametrize("fault", [None, "lane"])
def test_a_family_and_a_schedule_are_added_as_files(tmp_path, fault):
    """A second family (``cond`` a dict of a context sequence and a pooled
    vector, its own null prompt and program network) on a rectified-flow
    schedule, added to a copy of this directory as new files only
    (``testdata/additions``: family, schedule, configuration, traffic,
    limits) with ``BENCHMARK.json`` entries, serves to ``correct``; one
    wrong lane of eight in it is caught; no file that was there changes."""
    data = tmp_path / "chip"
    shutil.copytree(run.HERE, data, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    before = _file_digests(data)
    added = _file_digests(ADDITIONS)
    assert added and not set(added) & set(before)
    shutil.copytree(ADDITIONS, data, dirs_exist_ok=True)
    with open(TINY_BENCH) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-joint", "source": "a test",
                             "file": "chip/configs/toy-joint.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.backlog", "config": "toy-joint",
                               "traffic": "toy.backlog8", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "xl2-512.solve.backlog" in m.get("workloads", []):
            m["workloads"].append("toy.backlog")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.resolve("toy.backlog", bench_path=str(tmp_path /
                                                     "BENCHMARK.json"),
                       data_dir=str(data))
    if fault:
        cell.limits = dict(cell.limits, sample=10 ** 6)
        faults.REACHED.clear()
    undo = faults.plant(fault) if fault else None
    try:
        out, measured = _measure(cell, seconds=0.5)
    finally:
        if undo:
            undo()
    checks = out["checks"]
    if fault:
        assert faults.REACHED and not out["correct"], checks
        assert checks["x0_rel_err_max"]["value"] > \
            checks["x0_rel_err_max"]["limit"]
    else:
        assert out["correct"], checks
        assert checks["x0_rel_err_max"]["value"] < 1e-4
        assert set(out["metrics"]) == {"samples_per_s", "setup_s"}
        assert measured.forward_flops == cell.family.forward_flops(
            cell.config["model"], 16)
    now = _file_digests(data)
    assert {k: now[k] for k in before} == before
    assert set(now) == set(before) | set(added)


# -------------------------------------------------------- the trace
def test_interval_arithmetic():
    from benchmarks.chip import trace
    iv = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert iv == [(0, 3), (5, 8)]
    assert trace.length(iv) == 6
    assert trace.clip(iv, 2, 6) == [(2, 3), (5, 6)]
    assert trace.intersect(iv, [(2, 6)]) == [(2, 3), (5, 6)]


def test_scopes_name_the_scope_and_the_source_line():
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import trace

    def project(x, w):
        return x @ w

    def f(x, w):
        with jax.named_scope("backbone"):
            y = jnp.tanh(project(x, w))
        return y * 2.0

    x = jnp.ones((8, 8))
    text = jax.jit(f).lower(x, x).compile().as_text()
    (ops,) = trace.scopes([text])
    dots = [v for v in ops.values() if "dot_general" in v]
    assert dots and all("backbone" in v.split("/") for v in dots)
    line = project.__code__.co_firstlineno + 1
    assert all(f"@test_bench.py:{line} " in v and v.endswith("project")
               for v in dots), dots


@pytest.mark.parametrize("op_name, in_backbone, tail", [
    ("jit(run)/vmap()/while/body/closed_call/backbone/vmap()/add", True,
     "add"),
    ("jit(run)/vmap(backbone)/vmap()/while/body/closed_call/checkpoint/"
     "bsd,dhk->bshk/dot_general @transformer.py:271 TransformerLM._run_stack",
     True, "bsd,dhk->bshk/dot_general @transformer.py:271 "
     "TransformerLM._run_stack"),
    ("jit(run)/vmap()/while/body/closed_call/mul", False, "mul"),
    ("jit(run)/backbone_free/mul", False, "backbone_free/mul"),
])
def test_op_names_in_and_out_of_the_backbone(op_name, in_backbone, tail):
    from benchmarks.chip import trace
    assert bool(trace._BACKBONE.search(op_name)) is in_backbone
    assert trace._op_tail(op_name) == tail


def test_trace_reduction_on_a_recorded_chip_trace(tmp_path):
    """A 0.4 s window of ``xl2-256.solve.poisson`` traced on a TPU v5e,
    with the op names of the executables that run warmed."""
    import gzip
    from benchmarks.chip import trace
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "c1tiny.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with gzip.open(os.path.join(TESTDATA, "c1tiny.scopes.json.gz")) as f:
        op_scopes = json.load(f)
    r = trace.reduce(str(path), op_scopes=op_scopes)
    assert 0 < r["busy_s"] < r["window_s"]
    # leaf ops on one line do not overlap: their sum is their union
    assert r["op_s"] == pytest.approx(r["busy_s"], rel=1e-9)
    assert 0.5 * r["op_s"] < r["backbone_s"] < r["op_s"]
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    assert {n for n, _ in r["idle_gaps"]} <= set(trace.HOST_SPANS) | {"none"}
    whole = trace.reduce(str(path), demand=[(0.0, r["window_s"])],
                         op_scopes=op_scopes)
    assert whole["demand_busy_s"] == pytest.approx(r["busy_s"])
    assert trace.reduce(str(path))["backbone_s"] == 0.0
