"""The program's own host spans (`repro.obs`) in a traced window.

`jax.profiler.TraceAnnotation` records them on the thread that runs the
window, on the trace's clock, so `Trace.host_spans` holds them beside the
benchmark's `bench.window` and `bench.call`. The readers of the per-layer
metrics that they feed find them by exact name; a program without them,
such as one from before they were added, gives None.
"""
from __future__ import annotations


def in_window(trace, name: str) -> list:
    """(start_ns, end_ns) of each host span `name` inside the window."""
    w0, w1 = trace.window
    return [(s, e) for n, s, e in trace.host_spans
            if n == name and s >= w0 and e <= w1]


def total_ms(spans) -> float:
    return 1e-6 * sum(e - s for s, e in spans)


def per_build_ms(ctx, name: str):
    """Milliseconds of span `name` per re-assembly (`sched.build` span) in
    the window, or None where either span is absent."""
    builds = in_window(ctx["trace"], "sched.build")
    spans = in_window(ctx["trace"], name)
    if not builds or not spans:
        return None
    return total_ms(spans) / len(builds)
