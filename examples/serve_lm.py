"""Serve a small LM with batched requests (continuous slot batching).

Run: PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import lm
from repro.serving.engine import Request, ServeEngine
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

cfg = get_config("yi-6b", reduced=True)
params = lm.init_params(cfg, jax.random.PRNGKey(0))
engine = ServeEngine(cfg, params, slots=4, max_seq=128)

rng = np.random.default_rng(0)
requests = [
    Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(4, 24))),
            max_new_tokens=16)
    for i in range(12)
]
for r in requests:
    engine.submit(r)

t0 = time.time()
engine.run(max_ticks=500)
dt = time.time() - t0
total_tokens = sum(len(r.output) for r in requests)
print(f"served {len(requests)} requests / {total_tokens} tokens in {dt:.2f}s "
      f"({total_tokens / dt:,.1f} tok/s on CPU, 4-slot continuous batching)")
for r in requests[:3]:
    print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> {r.output[:8]}...")
