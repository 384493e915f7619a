"""Host microseconds per chunk in which no chip runs it: from the start of
the call that enqueues a chunk (`bench.dispatch`) to the return of the wait
for its result (`bench.block`), mean over the traced window's chunks, less
the mean time of one execution of the chunk program on a chip (`jit_chunk*`
on the `XLA Modules` line, over every chip). It holds JAX's dispatch, the
TPU runtime's launch and the completion signal reaching the host (layer:
host round trip).

Each term is a length on its own clock; no time on the chips' line is set
against one on the host's. The profiler's sync of the two lines was found
0.26 to 1 ms off on TPU v5e, as long as the round trip's parts, so the
trace cannot tell the wait before the chip starts from the wait after it
ends; their sum it can. A chunk is left out when a `bench.snapshot` span
starts between the end of the block before it and the end of its own: the
copies for the check of `correct` then run on the chips in its round trip.
None under MIN_CHUNKS chunks or without an execution of the program.
"""
PROGRAM = "jit_chunk"
#: fewer chunks than this give no number
MIN_CHUNKS = 20


def read(ctx):
    tr = ctx["trace"]
    spans = sorted(tr.spans, key=lambda s: s.start_ns)
    dispatches = [s for s in spans if s.name == "bench.dispatch"]
    blocks = [s for s in spans if s.name == "bench.block"]
    snapshots = [s.start_ns for s in spans if s.name == "bench.snapshot"]
    host, after = [], tr.t0
    for dispatch, block in zip(dispatches, blocks):
        if not any(after <= t < block.end_ns for t in snapshots):
            host.append(block.end_ns - dispatch.start_ns)
        after = block.end_ns
    runs = [m.end_ns - m.start_ns for m in tr.modules
            if m.name.startswith(PROGRAM)]
    if len(host) < MIN_CHUNKS or not runs:
        return None
    return (sum(host) / len(host) - sum(runs) / len(runs)) * 1e-3
