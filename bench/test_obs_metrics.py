"""The readers of the program's own spans (`bench/spans.py` and the
metrics that read it) on hand-built traces, and CPU rehearsals that show
the program's spans land on the window's thread."""
import pytest

from bench import run, spans, trace
from bench.test_rehearsal import cache_outside_the_checkout  # noqa: F401
from bench.test_rehearsal import rehearse

MS = 1_000_000  # ns


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py").read


def make_trace(*host, window=(0, 100 * MS)):
    """A Trace whose window thread ran `host` spans: (name, start ms,
    end ms)."""
    spans_ns = [(trace.WINDOW_SPAN, *window)] + [
        (n, s * MS, e * MS) for n, s, e in host]
    return trace.Trace(window, [], spans_ns, 1)


def ctx(tr, units=4):
    return {"trace": tr, "units": units, "counters": {}}


# two re-assemblies in the window, one before it
BUILDS = make_trace(
    ("sched.build", -30, -10), ("sched.schedule", -29, -25),
    ("op.pack", -24, -12),
    ("sched.build", 10, 30), ("sched.schedule", 10, 12),
    ("sched.construct", 10.5, 11.5),
    ("op.shard", 12, 15), ("op.pack", 15, 25), ("op.upload", 25, 29),
    ("sched.build", 50, 66), ("sched.schedule", 50, 51),
    ("op.shard", 51, 53), ("op.pack", 53, 61), ("op.upload", 61, 65),
    ("op.compile", 31, 34), ("op.dispatch", 70, 70.5),
    ("op.compile", 67, 70))


@pytest.mark.parametrize("name,want", [("schedule_ms", (2 + 1) / 2),
                                       ("shard_ms", (3 + 2) / 2),
                                       ("pack_ms", (10 + 8) / 2),
                                       ("upload_ms", (4 + 4) / 2)])
def test_build_metrics_are_per_reassembly_in_the_window(name, want):
    assert reader(name)(ctx(BUILDS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["schedule_ms", "shard_ms", "pack_ms",
                                  "upload_ms", "compile_ms",
                                  "level_host_ms"])
def test_absent_spans_read_none(name):
    # the benchmark's own spans alone, as a program without spans leaves
    tr = make_trace(("bench.call", 1, 40), ("bench.rebuild", 2, 20),
                    ("np.asarray", 30, 39))
    assert reader(name)(ctx(tr)) is None


def test_build_metrics_need_a_build_in_the_window():
    tr = make_trace(("op.pack", 10, 20))
    assert reader("pack_ms")(ctx(tr)) is None


def test_compile_ms_is_per_call_and_zero_when_calls_only_dispatch():
    assert reader("compile_ms")(ctx(BUILDS, units=5)) == pytest.approx(
        (3 + 3) / 5)
    dispatch_only = make_trace(("op.compile", -5, -1),
                               ("op.dispatch", 5, 5.1),
                               ("op.dispatch", 9, 9.1))
    assert reader("compile_ms")(ctx(dispatch_only)) == 0.0


def test_level_host_ms_leaves_out_each_levels_wait():
    tr = make_trace(
        ("bfs.level", -9, -1), ("bfs.wait", -8, -2),  # before the window
        ("bench.call", 0, 40),
        ("bfs.level", 1, 11), ("bfs.send", 1, 2), ("bfs.wait", 3, 10),
        ("bfs.update", 10, 11),
        ("bfs.level", 12, 20), ("bfs.wait", 13, 19),
        ("bfs.wait", 30, 35))                         # in no level
    assert reader("level_host_ms")(ctx(tr)) == pytest.approx(
        ((10 - 7) + (8 - 6)) / 2)


def test_in_window_keeps_whole_spans_by_exact_name():
    tr = make_trace(("op.pack", -1, 2), ("op.pack", 3, 4),
                    ("op.pack", 99, 101), ("op.packed", 5, 6))
    assert spans.in_window(tr, "op.pack") == [(3 * MS, 4 * MS)]


@pytest.fixture
def cpu_peaks(monkeypatch):
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def test_reassemble_rehearsal_reads_the_program_spans(cpu_peaks):
    r = rehearse("spmv-synthwiki.reassemble", trace=True)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ("schedule_ms", "shard_ms", "pack_ms", "upload_ms",
                 "compile_ms"):
        assert m[name] > 0, name


def test_graph500_rehearsal_reads_level_host_time(cpu_peaks):
    r = rehearse("bfs-kron20.graph500", trace=True)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["level_host_ms"] > 0
    assert m["compile_ms"] == 0.0
