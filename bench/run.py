#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload cartpole-rollout --seed 7 --seconds 10 \
        --trace 0

Everything is found by name. The cell is an entry of `BENCHMARK.json`'s
`workloads`; its configuration is `bench/configs/<config>.json`, its traffic
mix `bench/traffic/<traffic>.json`, the traffic's consumer (the window loop)
`bench/consumers/<consumer>.py`, the configuration's plain reference
`bench/reference/<reference>.py`, and each per-layer metric's reader
`bench/layer_metrics/<metric>.py`. A later cell adds files and entries and
edits none.

A run: set-up (the env state built on the device from `--seed`, every
shape of the cell compiled and warmed), then a window of `--seconds`
seconds, then the check of what the window produced against the plain
reference under `bench/reference/`. With `--trace 0` the result carries
the cell's end-to-end metrics; with `--trace 1` the window is traced and
the result carries its per-layer metrics, `busy_s`, `window_s` and a
breakdown. The numbers compared and their limits are the last lines on
stderr and the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: fixed places inside the checkout (git-ignored)
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: recorded for every program compiled or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: recorded for every program loaded from the persistent cache
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr)
        super().__init__(2)


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def since_start() -> str:
    return f"{time.perf_counter() - PROCESS_T0:.3f} s"


# -- finding things by name ---------------------------------------------------
def find_file(kind: str, name: str, suffix: str,
              roots: Sequence[pathlib.Path]) -> pathlib.Path:
    """`<root>/bench/<kind>/<name><suffix>` in the first root that has it."""
    for root in roots:
        path = root / "bench" / kind / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no bench/{kind}/{name}{suffix} under "
                            f"{[str(r) for r in roots]}")


def load_module(kind: str, name: str, roots: Sequence[pathlib.Path]):
    path = find_file(kind, name, ".py", roots)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str, roots: Sequence[pathlib.Path]) -> Dict:
    return json.loads(find_file(kind, name, ".json", roots).read_text())


def resolve(workload: str, roots: Sequence[pathlib.Path]) -> Dict:
    """Everything a cell needs, by name: its `BENCHMARK.json` entry, its
    configuration and traffic, its consumer module, the configuration's
    reference module, and its metrics with the reader module of each
    per-layer one."""
    bench = json.loads((roots[0] / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    config = load_json("configs", cell["config"], roots)
    traffic = load_json("traffic", cell["traffic"], roots)

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "consumer": load_module("consumers", traffic["consumer"], roots),
        "reference": load_module("reference", config["reference"], roots),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [(m, load_module("layer_metrics", m["name"], roots))
                      for m in bench["per_layer"] if mine(m)],
    }


# -- the device ----------------------------------------------------------------
def quiet_tpu_logs() -> None:
    """Keep the TPU runtime from writing its logs under a fixed `/tmp`
    path; call before JAX is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_chips(n: int):
    """The first `n` TPU devices; `NoChip` when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR` when set, else
    at the fixed `<checkout>/.jax_cache`; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs compiled (not found in the persistent cache) in
    each phase of a run ("set-up", "window")."""

    def __init__(self):
        import jax

        self.phase = "set-up"
        self.counts: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, seconds, **_: self._add(event, COMPILE_EVENT, 1))
        jax.monitoring.register_event_listener(
            lambda event, **_: self._add(event, CACHE_HIT_EVENT, -1))

    def _add(self, event: str, wanted: str, n: int) -> None:
        if event == wanted:
            self.counts[self.phase] = self.counts.get(self.phase, 0) + n


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_info(devices, peak: Optional[int]) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# -- one run ------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        roots: Sequence[pathlib.Path] = (ROOT,), chips=require_chips) -> Dict:
    """Set up, measure and check one cell; returns the result line."""
    import jax

    found = resolve(workload, roots)
    log(f"imported at {since_start()}")
    devices = chips(int(found["cell"]["chips"]))
    cache = enable_compile_cache()
    counter = CompileCounter()
    log(f"{workload}: device {devices[0].device_kind} x{len(devices)} "
        f"jax {jax.__version__} cache {cache}, at {since_start()}")

    cell = found["consumer"].Cell(found["config"], found["traffic"],
                                  found["reference"], seed, devices)
    log(f"programs built at {since_start()}")
    cell.warm()
    setup_s = time.perf_counter() - PROCESS_T0
    log(f"set-up {setup_s:.3f} s, compiles in set-up (none when every "
        f"program came from the cache): {counter.counts.get('set-up', 0)}")

    counter.phase = "window"
    trace_dir = TRACE_DIR / workload
    if trace:
        import jax.profiler

        shutil.rmtree(trace_dir, ignore_errors=True)
        with jax.profiler.trace(str(trace_dir)):
            stats = cell.window(seconds)
    else:
        stats = cell.window(seconds)
    counter.phase = "after"
    log(f"compiles inside the window: {counter.counts.get('window', 0)}")
    log(f"window: {stats['chunks']} chunks in {stats['seconds']:.6f} s")

    peak = memory_peak_bytes(devices)
    log(f"chunk near the time limit: {cell.close()} truncations")
    result: Dict = {"correct": None, "attempted": stats["chunks"],
                    "failed": 0, "metrics": {},
                    "device": device_info(devices, peak)}
    if trace:
        from trace_reduce import Trace, find_xplane

        tr = Trace.from_file(find_xplane(str(trace_dir)), cell.hlo_texts(),
                             found["config"].get("kernels", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(cell.layer_context(), trace=tr, stats=stats,
                   memory_peak_bytes=peak, device_kind=devices[0].device_kind)
        for m, reader in found["per_layer"]:
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.named_gaps(10)}
    else:
        e2e = dict(stats["end_to_end"], setup_s=setup_s)
        for m in found["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    checks, failed = cell.check()
    result["correct"] = failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    result["failed"] = failed
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    quiet_tpu_logs()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
