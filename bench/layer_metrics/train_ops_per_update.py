"""Device op executions per train step, per chip, under the train step's
four scopes (`cairl.act`, `cairl.env`, `cairl.replay`, `cairl.learn`): at
Table I's widths each op is small, so their count, each with its launch,
bounds the step."""
from learner_scopes import ops_per_update


def read(ctx):
    return ops_per_update(ctx)
