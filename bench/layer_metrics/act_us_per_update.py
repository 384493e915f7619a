"""Device microseconds per train step, per chip, under `cairl.act`:
the epsilon schedule, the Q forward on every lane's obs, the argmax,
the epsilon-greedy draws and choice, and the return bookkeeping
(`rl/dqn.make_train_step`)."""
from learner_scopes import us_per_update


def read(ctx):
    return us_per_update(ctx, "cairl.act")
