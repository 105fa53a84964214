"""Host time of one BFS level step outside the wait for the device: the
mean over `bfs.level` spans of the span less its `bfs.wait` child (the
transfers in, the dispatch, the host's level and frontier update)."""
from bench import spans


def read(ctx):
    tr = ctx["trace"]
    levels = spans.in_window(tr, "bfs.level")
    if not levels:
        return None
    waits = spans.in_window(tr, "bfs.wait")
    host = [(e - s) - sum(we - ws for ws, we in waits if s <= ws and we <= e)
            for s, e in levels]
    return 1e-6 * sum(host) / len(host)
