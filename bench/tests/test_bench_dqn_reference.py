"""The `dqn-cartpole-v1` reference against the fused trainer, at the
training goldens' sizes on the CPU: the program passes on seeded weights,
a chunk near the time limit included; planted faults in the learner, the
replay ring or the actor, and the reference itself in bfloat16 in the
program's place, read `correct` false.

The golden DQN setting (`golden_train_setup("dqn/CartPole-v1")`: a ring of
96 that wraps, learning from 16 stored transitions, a target sync every 13
steps, epsilon falling over 48) at 4 envs, on the cell's env engine; chunks
of 20 steps, so that each fits in the ring. Two chunks warm up; the third
(steps 40 to 59) passes epsilon's end and a sync; the fourth runs with
every lane's time counter set 1 to 4 steps short of the limit.
"""
import dataclasses
import json

import _paths
import jax
import jax.numpy as jnp
import pytest

import run
from repro.core import make
from repro.rl import dqn
from repro.train.fused import fused_train_chunk, golden_train_setup

ROOT = _paths.ROOT
NUM_ENVS, N, WARM = 4, 20, 2
REF = run.load_module("reference", "dqn_cartpole", (ROOT,))
CONFIG = json.loads((ROOT / "bench/configs/dqn-cartpole-v1.json").read_text())
LIMITS = CONFIG["limits"]


def golden():
    """(cfg, the cell's configuration at the golden sizes)."""
    _, _, cfg, _ = golden_train_setup("dqn/CartPole-v1")
    cfg = dataclasses.replace(cfg, num_envs=NUM_ENVS,
                              env_backend=CONFIG["backend"])
    config = dict(CONFIG, dqn=dict(CONFIG["dqn"], **{
        k: getattr(cfg, k) for k in (
            "memory_size", "learn_start", "batch_size", "exploration_steps",
            "target_update_freq")}))
    return cfg, config


CFG, SMALL = golden()
ENV = make("CartPole-v1")
BOUND = REF.bind(REF.hparams(SMALL, NUM_ENVS))
STEPS = jnp.arange(N, dtype=jnp.int32)
COPY = jax.jit(lambda s: jax.tree.map(jnp.copy, s))


@pytest.fixture(scope="module")
def judge():
    return jax.jit(BOUND.check)


def correct(gap, bad) -> bool:
    return float(gap) <= LIMITS["gap"] and int(bad) <= LIMITS["mismatches"]


def near_limit(state):
    """Every lane's time counter 1 to NUM_ENVS steps short of the limit."""
    t = REF.MAX_STEPS - 1 - jnp.arange(NUM_ENVS, dtype=jnp.int32)
    return BOUND.with_time(state, t)


def chunks(seed, wrap=None, apply_wrap=None):
    """Warm up, then (carry in, metrics, carry out) of the third chunk and
    of the fourth, run near the time limit. `wrap` wraps the train step,
    `apply_wrap` the Q network, to plant a fault."""
    state, apply_fn = dqn.dqn_init(ENV, CFG, jax.random.PRNGKey(seed))
    if apply_wrap is not None:
        apply_fn = apply_wrap(apply_fn)
    step_fn = dqn.make_train_step(ENV, apply_fn, CFG)
    if wrap is not None:
        step_fn = wrap(step_fn)
    run_chunk = fused_train_chunk(step_fn)
    for _ in range(WARM):
        state, _ = run_chunk(state, N)
    out = []
    for limit in (False, True):
        if limit:
            state = near_limit(state)
        before = COPY(state)
        state, metrics = run_chunk(state, N)
        out.append((before, metrics, COPY(state)))
    return out


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_the_program_passes(seed, judge):
    key = jax.random.PRNGKey(seed)
    gap, bad = jax.jit(BOUND.check_init)(dqn.dqn_init(ENV, CFG, key)[0], key)
    assert correct(gap, bad) and int(bad) == 0
    for before, metrics, after in chunks(seed):
        gap, bad = judge(before, STEPS, metrics, after)
        assert correct(gap, bad) and int(bad) == 0, (float(gap), int(bad))
    # the last chunk holds truncations, stored as not terminal
    assert int(jax.jit(BOUND.truncations)(before, STEPS, after)) >= 1


# -- planted faults -----------------------------------------------------------
#: the step whose update a fault leaves out: inside the third chunk
AT = WARM * N + 3


def where(c, new, old):
    return jax.tree.map(lambda n, o: jnp.where(c, n, o), new, old)


def skipped_adam_step(step_fn):
    def f(state, x, lr=None):
        new, m = step_fn(state, x)
        hit = state.step == AT
        return new._replace(params=where(hit, state.params, new.params),
                            opt=where(hit, state.opt, new.opt)), m
    return f


def target_never_syncs(step_fn):
    def f(state, x, lr=None):
        new, m = step_fn(state, x)
        return new._replace(target=state.target), m
    return f


def truncation_folded_into_terminal(step_fn):
    def f(state, x, lr=None):
        new, m = step_fn(state, x)
        cut = BOUND.time_counters(state) + 1 >= REF.MAX_STEPS
        at = (state.replay.ptr + jnp.arange(NUM_ENVS)) % CFG.memory_size
        done = new.replay.done.at[at].max(cut.astype(jnp.float32))
        return new._replace(replay=new.replay._replace(done=done)), m
    return f


def one_replay_entry_altered(step_fn):
    """The obs of the entry written last before the step at AT."""
    def f(state, x, lr=None):
        new, m = step_fn(state, x)
        slot = (state.replay.ptr - 1) % CFG.memory_size
        obs = new.replay.obs.at[slot, 0].add(
            jnp.where(state.step == AT, 0.5, 0.0))
        return new._replace(replay=new.replay._replace(obs=obs)), m
    return f


def action_flipped(apply_fn):
    """Lane 0's Q values swapped where the actor asks (a batch of every
    lane), so its greedy action is the other one; the learner's batches
    are left alone."""
    def f(params, x):
        q = apply_fn(params, x)
        return q.at[0].set(q[0, ::-1]) if x.shape[0] == NUM_ENVS else q
    return f


@pytest.mark.parametrize("wrap,apply_wrap", [
    (skipped_adam_step, None), (target_never_syncs, None),
    (truncation_folded_into_terminal, None), (one_replay_entry_altered, None),
    (None, action_flipped)],
    ids=["skipped_adam_step", "target_never_syncs",
         "truncation_folded_into_terminal", "one_replay_entry_altered",
         "action_flipped"])
def test_a_planted_fault_is_not_correct(wrap, apply_wrap, judge):
    readings = [judge(b, STEPS, m, a)
                for b, m, a in chunks(3, wrap, apply_wrap)]
    assert not all(correct(g, b) for g, b in readings), [
        (float(g), int(b)) for g, b in readings]


def test_the_action_check_is_exact_off_ties():
    """A flipped greedy action counts where the top two Q values are
    apart, and passes where they are within TIE_Q."""
    q = jnp.array([[1.0, 2.0], [3.0, 3.0 + REF.TIE_Q / 2]])
    greedy, gap, scale = REF._greedy(q)
    assert greedy.tolist() == [1, 1]
    assert (gap <= REF.TIE_Q * scale).tolist() == [False, True]


def test_the_bf16_control_is_not_correct(judge):
    control = jax.jit(lambda c, s: BOUND.run(c, s, jnp.bfloat16))
    sound = jax.jit(lambda c, s: BOUND.run(c, s, jnp.float32))
    for before, _, _ in chunks(5):
        metrics, after = control(before, STEPS)
        assert not correct(*judge(before, STEPS, metrics, after))
        # the reference in float32 in the program's place passes
        metrics, after = sound(before, STEPS)
        gap, bad = judge(before, STEPS, metrics, after)
        assert correct(gap, bad) and int(bad) == 0


# -- the cell's consumer, end to end ------------------------------------------
def test_the_consumer_runs_a_correct_cell(tmp_path, monkeypatch):
    """`bench/run.py`'s run of a cell over this consumer and reference, at
    the golden sizes, from new files in a temporary root."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    for kind in ("configs", "traffic"):
        (tmp_path / "bench" / kind).mkdir(parents=True)
    (tmp_path / "bench/configs/small.json").write_text(json.dumps(SMALL))
    (tmp_path / "bench/traffic/tiny.json").write_text(json.dumps(
        {"consumer": "dqn_train", "num_envs": NUM_ENVS,
         "train_steps_per_chunk": N, "check_chunks": 2, "check_range": 4}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "c", "config": "small", "traffic": "tiny",
                           "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result = run.run("c", 2 ** 33 + 5, 1.0, False, roots=(tmp_path, ROOT),
                     chips=lambda n: jax.devices()[:n])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["mismatches"]["value"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}


def test_the_configuration_is_table_i():
    """The cell's learner settings are `configs.cairl_dqn.PAPER_TABLE_I`'s,
    with the repo's defaults where Table I gives none."""
    from repro.configs.cairl_dqn import PAPER_TABLE_I
    from repro.train.optim import Adam

    for k, v in CONFIG["dqn"].items():
        want = getattr(PAPER_TABLE_I, k)
        assert (list(want) if isinstance(want, tuple) else want) == v, k
    adam = Adam()
    o = CONFIG["optimizer"]
    assert (o["b1"], o["b2"], o["eps"]) == (adam.b1, adam.b2, adam.eps)
