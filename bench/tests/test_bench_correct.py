"""`correct`, at a size a test run can hold: the program passes, the
control (the reference in bfloat16 in the program's place) fails, and a run
whose timed path is broken underneath reads `correct` false.

The cells here are new files in a temporary directory: small traffic over
the real configurations, and a consumer that wraps the real one with one
fault. The harness's look for a chip is skipped; on a CPU `backend="auto"`
runs the megastep's and the rasteriser's jnp paths.
"""
import json

import _paths
import jax
import pytest

import control
import run

ROOT = _paths.ROOT
TINY = {
    "cartpole-v1": {"num_envs": 256, "unroll": 8},
    "pong-v0": {"num_envs": 8, "unroll": 2},
}
#: the checked chunks lie among the window's first CHECK_RANGE
CHECK_RANGE = 8
#: long enough that a loaded CPU reaches the checked chunks
WINDOW_S = 2.0

FAULTY = '''
import importlib.util
import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_consumers_rollout_wrapped", {real!r})
real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(real)
FAULT = {fault!r}


_real_step = real.env_step


def faulty(pool):
    step = _real_step(pool)

    def f(carry, acts):
        new, out = step(carry, acts)
        b = acts.shape[1]
        if FAULT == "unchanged":
            return carry, out
        if FAULT == "half":
            keep = jnp.arange(b) < b // 2
            lane = lambda n, o: (jnp.where(
                keep.reshape((b,) + (1,) * (n.ndim - 1)), n, o)
                if n.ndim and n.shape[0] == b else n)
            zero = lambda x: jnp.where(
                keep.reshape((1, b) + (1,) * (x.ndim - 2)), x,
                jnp.zeros_like(x))
            return (jax.tree.map(lane, new, carry),
                    jax.tree.map(zero, out))
        if FAULT == "reward":
            return new, out._replace(reward=out.reward.at[0, 0].add(1.0))
        if FAULT == "obs":
            return new, out._replace(obs=out.obs.at[0, 0].add(1e-2))
        if FAULT == "truncated":
            return new, out._replace(info=dict(
                out.info, truncated=jnp.zeros_like(out.info["truncated"])))
        raise ValueError(FAULT)
    return f


real.env_step = faulty
Cell = real.Cell
'''


def make_root(tmp_path, config, consumer="rollout", fault=None):
    """A root holding one small cell `c` over `config`."""
    (tmp_path / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp_path / "bench" / "consumers").mkdir(exist_ok=True)
    if fault is not None:
        consumer = f"rollout_{fault}"
        (tmp_path / "bench" / "consumers" / f"{consumer}.py").write_text(
            FAULTY.format(real=str(ROOT / "bench" / "consumers" /
                                   "rollout.py"), fault=fault))
    traffic = dict(TINY[config], consumer=consumer, policy="uniform",
                   check_chunks=2, check_range=CHECK_RANGE)
    (tmp_path / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "c", "config": config, "traffic": "tiny",
                           "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return (tmp_path, ROOT)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """The tests leave JAX's persistent cache as the process had it."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")


def one_run(roots, seed=2 ** 31 + 7):
    return run.run("c", seed, WINDOW_S, False, roots=roots,
                   chips=lambda n: jax.devices()[:n])


@pytest.mark.parametrize("config", sorted(TINY))
def test_program_is_correct(tmp_path, config):
    result = one_run(make_root(tmp_path, config))
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["mismatches"]["value"] == 0
    assert set(result["metrics"]) == {"env_steps_per_s", "setup_s"}


@pytest.mark.parametrize("config", sorted(TINY))
def test_control_is_not_correct(tmp_path, config):
    found = run.resolve("c", make_root(tmp_path, config))
    limits = found["config"]["limits"]
    cell = found["consumer"].Cell(found["config"], found["traffic"],
                                  found["reference"], 11, jax.devices()[:1])
    cell.warm()
    cell.window(WINDOW_S)
    assert cell.close() > 0
    assert cell.layer_context()["interface_bytes"] > 0
    for gap, bad, c_gap, c_bad in control.readings(cell):
        assert gap <= limits["gap"] and bad <= limits["mismatches"]
        assert c_gap > limits["gap"] or c_bad > limits["mismatches"]


@pytest.mark.parametrize("config,fault", [
    ("cartpole-v1", "unchanged"), ("cartpole-v1", "half"),
    ("cartpole-v1", "reward"), ("cartpole-v1", "obs"),
    ("cartpole-v1", "truncated"), ("pong-v0", "unchanged"),
    ("pong-v0", "obs"), ("pong-v0", "truncated")])
def test_broken_step_is_not_correct(tmp_path, config, fault):
    result = one_run(make_root(tmp_path, config, fault=fault))
    assert result["correct"] is False and result["failed"] >= 1
