"""Host time of one re-assembly inside the window: the benchmark's own span
around `LoopScheduler.build` (schedule cache lookup, pack_csr, upload,
op construction)."""


def read(ctx):
    spans = ctx["counters"].get("rebuild_s") or []
    return 1e3 * sum(spans) / len(spans) if spans else None
