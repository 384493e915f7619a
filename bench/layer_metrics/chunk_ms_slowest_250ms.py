"""Host milliseconds per chunk, dispatch to ready, in the slowest stretch
of the window: the chunks are cut into runs of consecutive chunks that
each span at least 250 ms on the host clock, from the first chunk's
dispatch to the last one's ready, and the run with the most time per chunk
gives the number. A stall in dispatch, in the block or on the device moves
it, where the window's rate averages it away. Runs that hold a chunk
copied for the check of `correct` are left out, and so is the last, short
run."""

SPAN_S = 0.25


def read(ctx):
    stats = ctx["stats"]
    marks = stats.get("chunk_marks") or []
    snapped = set(stats.get("snapshot_chunks", ()))
    worst, first = None, 0
    for i, (_, ready) in enumerate(marks):
        span = ready - marks[first][0]
        if span < SPAN_S:
            continue
        if not snapped.intersection(range(first, i + 1)):
            per_chunk = span / (i + 1 - first) * 1e3
            worst = per_chunk if worst is None else max(worst, per_chunk)
        first = i + 1
    return worst
