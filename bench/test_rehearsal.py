"""A tiny CPU rehearsal of each cell (Pallas in interpret mode), the
fault runs that must come out not correct, the control at test size, and
the command's refusals. None of them prints a result line."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run
from repro import compile_cache, sched
from repro.sched import kernels as K

TINY = {"spmv": {"config": {"n_rows": 3000}},
        "bfs": {"config": {"scale": 10}, "traffic": {"eccentricity": 4}}}
CELLS = [w["name"] for w in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(autouse=True, scope="module")
def cache_outside_the_checkout(tmp_path_factory):
    """The CPU rehearsals compile into a cache of their own: entries that a
    CPU run leaves in the checkout's cache would travel with the checkout
    to the chip's machine."""
    mp = pytest.MonkeyPatch()
    mp.setattr(compile_cache, "REPO_CACHE_DIR",
               tmp_path_factory.mktemp("jax_cache"))
    yield
    mp.undo()


def tiny(name):
    return TINY[run.load_cell(name)["config"]["kind"]]


def rehearse(name, seed=2**31 + 3, trace=False):
    return run.run_cell(name, seed, 0.3, trace, time.perf_counter(),
                        overrides=tiny(name))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_on_cpu(name, capsys):
    r = rehearse(name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    want = {m["name"] for m in run.load_cell(name)["end_to_end"]}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    out = capsys.readouterr().out.strip().splitlines()
    assert not any(line.startswith("{") for line in out)


def test_traced_cell_reads_counters_on_cpu(monkeypatch):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    r = rehearse("spmv-synthwiki.reassemble", trace=True)
    assert r["correct"]
    assert 0 < r["metrics"]["slot_fill"]["value"] <= 100
    assert r["metrics"]["repack_ms"]["value"] > 0
    assert r["device"]["window_s"] > 0 and "breakdown" in r


@pytest.mark.parametrize("name", ["spmv-synthwiki.power",
                                  "bfs-kron20.graph500"])
def test_schedule_shapes_do_not_depend_on_the_seed(name):
    """Every seed builds the same schedule, so only a checkout's first run
    compiles the kernel."""
    cell = run.load_cell(name, tiny(name))
    kind = run.kind_module(cell["config"]["kind"])
    shapes = []
    for seed in (5, 2**31 + 9):
        wl = kind.Workload(cell["config"], cell["traffic"], seed, sched)
        wl.setup()
        c = wl.counters()
        shapes.append([c[k] for k in ("slots", "tiles", "width",
                                      "blocks_per_worker")])
    assert shapes[0] == shapes[1]


def _spmv_fault(kind):
    real = K.SpmvOp.__call__

    def call(self, x, interpret=None):
        y = real(self, x, interpret)
        if kind == "state_unchanged":
            return x
        if kind == "half_left_out":
            return y.at[y.shape[0] // 2:].set(0.0)
        return y.at[7].add(1.0)              # answer altered
    return call


def _bfs_fault(kind):
    real_step, real_levels = K.BfsOp.step, K.BfsOp.levels

    def step(self, frontier, visited, interpret=None):
        nxt = real_step(self, frontier, visited, interpret)
        if kind == "state_unchanged":
            return jnp.zeros_like(nxt)
        return nxt.at[nxt.shape[0] // 2:].set(0.0)   # half left out

    def levels(self, source=0, interpret=None):
        lv = real_levels(self, source, interpret)
        lv[np.flatnonzero(lv > 0)[0]] += 1            # answer altered
        return lv
    return ("levels", levels) if kind == "answer_altered" else ("step", step)


FAULTS = ["state_unchanged", "half_left_out", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    if run.load_cell(name)["config"]["kind"] == "spmv":
        monkeypatch.setattr(K.SpmvOp, "__call__", _spmv_fault(fault))
    else:
        attr, fn = _bfs_fault(fault)
        monkeypatch.setattr(K.BfsOp, attr, fn)
    r = rehearse(name, seed=11)
    assert not r["correct"] and r["failed"] > 0


@pytest.mark.parametrize("name", ["spmv-synthwiki.power",
                                  "bfs-kron20.graph500"])
def test_control_is_not_correct(name):
    seeds = [1, 2, 3]
    got = list(control.readings(name, seeds, 0.2, overrides=tiny(name)))
    assert [r["seed"] for r in got] == seeds
    for r in got:
        for k, (v, lim) in r["program"].items():
            assert v <= lim, (k, v, lim)
            ctl, _ = r["control"][k]
            assert ctl > lim, (k, ctl, lim)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "spmv-synthwiki.power", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_without_tpu_exits_nonzero_and_prints_nothing():
    p = _cli(run.ROOT)
    assert p.returncode == 2 and p.stdout == ""


def test_command_with_only_benchmark_files_fails(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
