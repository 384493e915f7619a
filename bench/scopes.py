"""Device time of the env step's named scopes (`cairl.*`, put by the
program around each sub-layer of the fused step)."""
from typing import Optional


def scope_us_per_chunk(ctx, scope: str) -> Optional[float]:
    """Device self microseconds per chunk, per chip, of the ops whose scope
    path holds `scope`; None when no op does (a program without the scope).
    Only the scope is asked for, not `bench.env_step` too: the ops of a
    nested `jit` can carry a shortened path."""
    tr = ctx["trace"]
    ops = tr.ops(scope=scope)
    if not ops:
        return None
    return tr.seconds(ops) / ctx["stats"]["chunks"] * 1e6
