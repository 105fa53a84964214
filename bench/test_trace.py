"""The trace reducer on two small traces recorded on a TPU v5e chip
(bench/testdata): three SpMV calls at 200,000 rows, one BFS traversal at
scale 14, each in a `bench.window` span."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "testdata"


@pytest.fixture(scope="module", params=["spmv", "bfs"])
def recorded(request):
    return request.param, trace.Trace.from_file(
        DATA / f"{request.param}_small.xplane.pb")


def test_device_time_adds_up(recorded):
    _, t = recorded
    assert t.n_devices == 1 and t.device_ops
    assert 0 < t.busy_s() <= t.window_s()
    # kernels and the rest partition the device ops; ops never overlap on
    # one chip's "XLA Ops" line, so their sum is the busy time
    assert t.kernel_s() + t.xla_s() == pytest.approx(t.busy_s(), rel=1e-9)


def test_kernels_found_by_opcode(recorded):
    name, t = recorded
    kernels = [n for _, n, *_, k in t.device_ops if k]
    # one Mosaic custom call per SpMV call / per BFS level step
    assert len(kernels) == {"spmv": 3, "bfs": 5}[name]
    assert all(n.endswith(" custom-call") for n in kernels)
    assert t.kernel_s() > 0


def test_recorded_numbers(recorded):
    name, t = recorded
    want = {"spmv": (0.183777667, 0.167325161, 0.004744254),
            "bfs": (0.058791706, 0.047312683, 0.000603678)}[name]
    assert (t.window_s(), t.busy_s(), t.kernel_s()) == pytest.approx(
        want, rel=1e-6)


def test_breakdown_names_the_gather_and_the_host(recorded):
    name, t = recorded
    b = t.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion fusion"   # XLA's gather x[cols]
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        t.window_s() - t.busy_s(), rel=1e-6)
    assert all(n != trace.WINDOW_SPAN for n, _ in b["idle_gaps"])


def test_op_label_and_is_kernel():
    fusion = ("%fusion = f32[123]{0:T(1024)} fusion(f32[7]{0:T(1024)S(1)} "
              "%copy-done, s32[123]{0:T(1024)} %b), kind=kCustom")
    call = ('%_unknown_.1 = (f32[4,8,128]{2,1,0:T(8,128)}, f32[4,1,128]) '
            'custom-call(s32[6]{0} %f), custom_call_target="tpu_custom_call"')
    assert trace.op_label(fusion) == "fusion fusion"
    assert trace.op_label(call) == "_unknown_.1 custom-call"
    assert not trace.is_kernel(fusion) and trace.is_kernel(call)
