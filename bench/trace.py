"""The profiler trace of a run's window, reduced to device time.

`Tracer` records the window with `jax.profiler` (host spans of the
benchmark's own annotations, no Python tracer) into a temporary directory.
`Trace` reads the `.xplane.pb` it wrote with `jax.profiler.ProfileData`:

* device ops: the events of each chip's "XLA Ops" line (planes named
  `/device:TPU:<i>`), clipped to the window;
* kernels: the device ops whose HLO opcode is `custom-call` with target
  `tpu_custom_call`, which is how a Pallas (Mosaic) kernel appears,
  whatever its name (an event's name is its HLO instruction's text);
* host spans: the events of the host thread that ran the window, used to
  name what the host was doing in each idle gap of the device.

Times are in nanoseconds on the trace's own clock, where the host and
device planes are aligned by the profiler.
"""
from __future__ import annotations

import glob
import re
import shutil
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class Tracer:
    """Records the window into `log_dir` and reads it back."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self) -> "Trace":
        paths = glob.glob(f"{self.log_dir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace in {self.log_dir}, "
                               f"found {paths}")
        return Trace.from_file(paths[0])

    def cleanup(self) -> None:
        shutil.rmtree(self.log_dir, ignore_errors=True)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


# "%name = <shape> opcode(operands), attributes"; shapes hold no
# whitespace followed by a lower-case word and "(".
_HLO = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9-]*)\(")


def op_label(text: str) -> str:
    """"name opcode" of an HLO instruction's text."""
    m = _HLO.match(text)
    return f"{m[1]} {m[2]}" if m else text[:80]


def is_kernel(text: str) -> bool:
    m = _HLO.match(text)
    return bool(m) and m[2] == "custom-call" and \
        'custom_call_target="tpu_custom_call"' in text


class Trace:
    def __init__(self, window, device_ops, host_spans, n_devices):
        self.window = window              # (start_ns, end_ns)
        self.device_ops = device_ops      # [(device, name, start, end, kernel)]
        self.host_spans = host_spans      # [(name, start, end)]
        self.n_devices = n_devices

    @classmethod
    def from_file(cls, path: str | Path) -> "Trace":
        import jax
        data = jax.profiler.ProfileData.from_file(str(path))
        window, host, ops, devices = None, [], [], set()
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    devices.add(plane.name)
                    for ev in line.events:
                        ops.append((plane.name, op_label(ev.name),
                                    ev.start_ns, ev.start_ns + ev.duration_ns,
                                    is_kernel(ev.name)))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans = [(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events]
                    for name, s, e in spans:
                        if name == WINDOW_SPAN:
                            # the thread that ran the window
                            window, host = (s, e), spans
        if window is None:
            raise RuntimeError(f"no {WINDOW_SPAN!r} span in {path}")
        w0, w1 = window
        ops = [(d, n, max(s, w0), min(e, w1), k) for d, n, s, e, k in ops
               if e > w0 and s < w1]
        return cls(window, ops, host, max(len(devices), 1))

    # ------------------------------------------------------------ totals
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device):
        return _union((s, e) for d, _, s, e, _ in self.device_ops
                      if d == device)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        devices = {d for d, *_ in self.device_ops}
        total = sum(e - s for d in devices
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / self.n_devices

    def kernel_s(self):
        if not self.device_ops:
            return None
        return sum(e - s for *_, s, e, k in self.device_ops if k) * 1e-9 \
            / self.n_devices

    def xla_s(self):
        if not self.device_ops:
            return None
        return sum(e - s for *_, s, e, k in self.device_ops if not k) \
            * 1e-9 / self.n_devices

    # --------------------------------------------------------- breakdown
    def idle_gaps(self):
        """[(start, end)] of the windows's stretches with no device op on
        the first chip."""
        devices = sorted({d for d, *_ in self.device_ops})
        busy = self.busy_intervals(devices[0]) if devices else []
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def host_activity(self, t: float) -> str:
        """Name of the innermost host span (other than the window's)
        around time t, or "host idle"."""
        best = None
        for name, s, e in self.host_spans:
            if name != WINDOW_SPAN and s <= t < e and (
                    best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "host idle"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the idle gaps summed by
        what the host was doing in them, both in seconds."""
        by_op = defaultdict(float)
        for _, name, s, e, _ in self.device_ops:
            by_op[name] += (e - s) * 1e-9
        by_host = defaultdict(float)
        for s, e in self.idle_gaps():
            by_host[self.host_activity((s + e) / 2)] += (e - s) * 1e-9
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {"device_ops": [[k, v] for k, v in order(by_op)],
                "idle_gaps": [[k, v] for k, v in order(by_host)]}
