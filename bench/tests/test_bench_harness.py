"""`BENCHMARK.json` against its contract, every cell resolved by name, a
cell added from new files alone, and no result without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import _paths
import pytest

import run

ROOT = _paths.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape(bench):
    assert set(bench) == TOP_KEYS
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len(configs) == len(bench["configs"])
    assert len(cells) == len(bench["workloads"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves_by_name(bench):
    for w in bench["workloads"]:
        found = run.resolve(w["name"], (ROOT,))
        assert found["config"]["name"] == w["config"]
        assert hasattr(found["consumer"], "Cell")
        assert callable(found["reference"].check)
        assert {m["name"] for m in found["end_to_end"]} >= {
            "setup_s", "env_steps_per_s"}
        assert found["per_layer"]
        for _, reader in found["per_layer"]:
            assert callable(reader.read)


def test_a_new_cell_needs_only_new_files(tmp_path, bench):
    """A later PR adds a traffic file and an entry; nothing is edited."""
    extra = dict(bench)
    extra["workloads"] = bench["workloads"] + [{
        "name": "cartpole-rollout-k1", "config": "cartpole-v1",
        "traffic": "rollout-16384x1", "chips": 1, "why": "K=1 launches"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(extra))
    traffic = tmp_path / "bench" / "traffic"
    traffic.mkdir(parents=True)
    (traffic / "rollout-16384x1.json").write_text(json.dumps(
        {"consumer": "rollout", "num_envs": 16384, "unroll": 1,
         "policy": "uniform", "check_chunks": 1}))
    found = run.resolve("cartpole-rollout-k1", (tmp_path, ROOT))
    assert found["traffic"]["unroll"] == 1
    assert found["config"]["env_id"] == "CartPole-v1"
    with pytest.raises(KeyError, match="no workload"):
        run.resolve("no-such-cell", (tmp_path, ROOT))


def _no_result(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cartpole-rollout",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    return proc


def test_no_tpu_no_result(tmp_path):
    proc = _no_result(ROOT, tmp_path)
    assert proc.returncode == 2 and "needs a TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A checkout with only `BENCHMARK.json` and `bench/` has no system to
    measure."""
    alone = tmp_path / "alone"
    shutil.copytree(ROOT / "bench", alone / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    _no_result(alone, tmp_path)


def test_seed_key_takes_large_seeds():
    rollout = run.load_module("consumers", "rollout", (ROOT,))
    def key(seed):
        return rollout.key_from_parts(*rollout.seed_parts(seed))

    big = key(2 ** 33 + 5)
    assert not (big == key(5)).all()
    assert (key(2 ** 33 + 5) == big).all()
