"""Device microseconds per train step, per chip, under `cairl.replay`:
the step's transitions written into the replay ring and the
learner's batch drawn from it (`rl/replay.py`)."""
from learner_scopes import us_per_update


def read(ctx):
    return us_per_update(ctx, "cairl.replay")
