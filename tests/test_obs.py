"""Host spans and device scopes of the loop-call path (`repro.obs`).

The host spans are recorded by a stand-in for `obs.span` that keeps each
span's name, its enclosing span and its arguments; the device scopes and
program names are read from the lowered programs' debug locations.
"""
import contextlib
import re
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import random_csr

from repro import obs, sched

SCOPES = ("ich.gather", "ich.payload", "ich.relayout", "ich.kernel",
          "ich.fold")


class Recorder:
    """Records (name, parent name, args) of every span, in entry order."""

    def __init__(self):
        self.events = []
        self._open = []

    @contextlib.contextmanager
    def span(self, name, **args):
        self.events.append((name, self._open[-1] if self._open else None,
                            args))
        self._open.append(name)
        try:
            yield
        finally:
            self._open.pop()

    def take(self):
        out, self.events = self.events, []
        return out


@pytest.fixture
def spans(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(obs, "span", rec.span)
    return rec


def undirected_graph(n=200, m=600, seed=0):
    rng = np.random.default_rng(seed)
    a, b = rng.integers(0, n, (2, m))
    keep = a != b
    rows = np.concatenate([a[keep], b[keep]])
    cols = np.concatenate([b[keep], a[keep]])
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n))])
    return indptr, cols.astype(np.int32)


def test_build_spans_nest_and_a_miss_constructs_once(spans):
    indptr, indices, data = random_csr(300, seed=1)
    scheduler = sched.LoopScheduler(p=2)
    scheduler.build("spmv", indptr, indices, data)
    miss = spans.take()
    # a re-assembly: the same pattern with new values hits the cache
    scheduler.build("spmv", indptr, indices, data * 2.0)
    hit = spans.take()
    assert hit == [("sched.build", None, {"workload": "spmv"}),
                   ("sched.schedule", "sched.build", {}),
                   ("op.shard", "sched.build", {}),
                   ("op.pack", "sched.build", {}),
                   ("op.upload", "sched.build", {})]
    assert [e for e in miss if e[0] == "sched.construct"] == [
        ("sched.construct", "sched.schedule", {})]
    assert [e for e in miss if e[0] != "sched.construct"] == hit


def test_direct_schedule_has_its_own_span(spans):
    scheduler = sched.LoopScheduler(p=2, cache_size=0)
    scheduler.schedule(np.arange(1, 40))
    assert spans.take() == [("sched.schedule", None, {}),
                            ("sched.construct", "sched.schedule", {})]


def test_first_call_compiles_and_later_calls_dispatch(spans):
    indptr, indices, data = random_csr(300, seed=2)
    op = sched.LoopScheduler(p=2).build("spmv", indptr, indices, data)
    spans.take()
    x = jnp.ones(300, jnp.float32)
    for _ in range(3):
        op(x, interpret=True)
    assert spans.take() == [("op.compile", None, {"program": "ich_spmv"})] \
        + [("op.dispatch", None, {"program": "ich_spmv"})] * 2


def test_each_bfs_level_step_has_its_wait(spans):
    indptr, indices = undirected_graph()
    op = sched.LoopScheduler(p=2).build("bfs", indptr, indices)
    spans.take()
    level = op.levels(0, interpret=True)
    events = spans.take()
    steps = int(level.max()) + 1  # the last step finds no new vertex
    assert events[0] == ("bfs.levels", None, {"source": 0})
    assert [e for e in events if e[0] == "bfs.level"] == [
        ("bfs.level", "bfs.levels", {"depth": d})
        for d in range(1, steps + 1)]
    per_level = [e for e in events if e[1] == "bfs.level"]
    program = {"program": "ich_bfs_step"}
    assert per_level == [
        ("bfs.send", "bfs.level", {}),
        ("op.compile", "bfs.level", program),
        ("bfs.wait", "bfs.level", {}),
        ("bfs.update", "bfs.level", {})] + [
        ("bfs.send", "bfs.level", {}),
        ("op.dispatch", "bfs.level", program),
        ("bfs.wait", "bfs.level", {}),
        ("bfs.update", "bfs.level", {})] * (steps - 1)


def test_span_is_a_profiler_annotation_once_jax_is_loaded():
    import jax
    assert isinstance(obs.span("a", depth=1), jax.profiler.TraceAnnotation)


def test_schedule_construction_does_not_import_jax():
    code = ("import sys\n"
            "import numpy as np\n"
            "import repro.sched as S\n"
            "from repro import obs\n"
            "S.LoopScheduler(p=4).schedule(np.arange(1, 500))\n"
            "assert obs.span('a') is obs.span('b', depth=2)\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _spmv_program(interpret):
    indptr, indices, data = random_csr(300, seed=3)
    op = sched.LoopScheduler(p=2).build("spmv", indptr, indices, data)
    args = (op.vals, op.cols, op.rowid, op.blkid,
            jnp.ones(300, jnp.float32))
    return "ich_spmv", op._program(interpret), args, {
        "slot_cost": op.slot_cost}


def _bfs_program(interpret):
    indptr, indices = undirected_graph()
    op = sched.LoopScheduler(p=2).build("bfs", indptr, indices)
    f = jnp.zeros(op.n, jnp.float32).at[0].set(1.0)
    args = (op.mask, op.cols, op.rowid, op.blkid, f, f)
    return "ich_bfs_step", op._program(interpret), args, {
        "slot_cost": op.slot_cost}


def _kmeans_program(interpret):
    rng = np.random.default_rng(4)
    op = sched.LoopScheduler(p=2).build("kmeans", rng.random(200) * 8)
    args = (jnp.asarray(rng.random((200, 3)), jnp.float32),
            jnp.asarray(rng.random((4, 3)), jnp.float32), op.rowid)
    return "ich_kmeans_assign", op._program(interpret), args, {
        "slot_cost": op.slot_cost}


def _tpu_locations(make):
    """Program name and the debug text of its lowering for a TPU (the
    compiled kernel's own program, not the interpreter's)."""
    name, prog, args, kw = make(False)
    low = prog.trace(*args, **kw).lower(lowering_platforms=("tpu",))
    return name, low.as_text(debug_info=True)


@pytest.mark.parametrize("make", [_spmv_program, _bfs_program,
                                  _kmeans_program])
def test_programs_have_stable_names(make):
    name, prog, args, kw = make(True)
    text = prog.lower(*args, **kw).as_text()
    assert text.startswith(f"module @jit_{name} ")


@pytest.mark.parametrize("make", [_spmv_program, _bfs_program])
def test_every_op_sits_under_an_ich_scope(make):
    name, text = _tpu_locations(make)
    assert f"\nmodule @jit_{name} " in text
    prefix = f"jit({name})/"
    op_names = [n for n in re.findall(r'loc\("([^"]+)"', text)
                if n.startswith(prefix)]
    assert op_names
    scopes = {n[len(prefix):].split("/")[0] for n in op_names}
    assert scopes == set(SCOPES)


@pytest.mark.parametrize("make,kernel", [(_spmv_program, "ich_reduce_add"),
                                         (_bfs_program, "ich_reduce_max")])
def test_kernel_is_named(make, kernel):
    _, text = _tpu_locations(make)
    calls = re.findall(r'custom_call @tpu_custom_call.*kernel_name = "(\w+)"',
                       text)
    assert calls == [kernel]
