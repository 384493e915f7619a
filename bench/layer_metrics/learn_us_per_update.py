"""Device microseconds per train step, per chip, under `cairl.learn`:
the TD loss and its gradient, the Adam step, the selects that skip
them while the ring warms up, and the target sync (`rl/dqn.py`,
`train/optim.py`)."""
from learner_scopes import us_per_update


def read(ctx):
    return us_per_update(ctx, "cairl.learn")
