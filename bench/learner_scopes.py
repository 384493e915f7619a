"""Device time and op count of the DQN train step's named scopes
(`cairl.act`, `cairl.env`, `cairl.replay`, `cairl.learn`, put by
`rl/dqn.make_train_step`), per chip per train step.

The train step runs some 90 small device ops, so a traced window of the
cell holds millions, and the profiler keeps only the first several seconds
of them (on TPU v5e, about 5 s of a 10 s window). Per-step values are
therefore taken over the train steps the trace holds, not over the
window's: one execution of the chunk program is one event of its scan's
`while` op, the one loop outside every `cairl.` scope.
"""
from typing import Dict, Optional, Tuple

SCOPES = ("cairl.act", "cairl.env", "cairl.replay", "cairl.learn")
#: the op_name of the chunk program's scan ends so
LOOP = "/while"


def split(ctx) -> Optional[Dict[str, Tuple[float, float]]]:
    """{scope: (device self us, op executions)} per chip per train step;
    None when the trace holds no scan or no op under the scopes. Computed
    once per trace."""
    tr = ctx["trace"]
    cached = getattr(tr, "learner_split", False)
    if cached is not False:
        return cached
    ns = dict.fromkeys(SCOPES, 0.0)
    n = dict.fromkeys(SCOPES, 0)
    loops = 0
    for o in tr.ops():
        held = [s for s in SCOPES if s in o.op_name]
        if held:
            ns[held[0]] += o.self_ns
            n[held[0]] += 1
        elif o.op_name.endswith(LOOP) and "cairl." not in o.op_name:
            loops += 1
    steps = loops / tr.n_devices * ctx["train_steps_per_chunk"]
    out = None
    if steps and any(n.values()):
        per = tr.n_devices * steps
        out = {s: (ns[s] * 1e-3 / per, n[s] / per) for s in SCOPES}
    tr.learner_split = out
    return out


def us_per_update(ctx, scope: str) -> Optional[float]:
    got = split(ctx)
    return None if got is None else got[scope][0]


def ops_per_update(ctx) -> Optional[float]:
    got = split(ctx)
    return None if got is None else sum(c for _, c in got.values())
