import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf-iteration harness (§Perf): compile a cell under a named VARIANT of
the tunable knobs and report the roofline-term deltas vs. baseline.

Knobs exposed (each one maps to a hypothesis in EXPERIMENTS.md §Perf):
  remat            : none | dots | full          (compute <-> memory trade)
  ce_chunk         : loss-chunk length           (CE temp memory)
  q_chunk          : attention query-chunk       (attention temp memory)
  accum            : gradient-accumulation steps (collective amortisation)
  seq_shard_decode : shard decode cache seq over model axis when heads can't
                     be TP-sharded (collective <-> memory trade)
  dtype            : activation dtype

Usage:
  PYTHONPATH=src python -m repro.launch.perf --arch olmoe-1b-7b \
      --shape train_4k --variant remat=dots,accum=4
"""
import argparse
import dataclasses
import json
import time
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import shape_by_name
from repro.configs.registry import ARCH_IDS, get_config, input_specs
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.sharding import rules
from repro.train.trainer import TrainConfig, make_optimizer, make_train_step

_KNOB_DEFAULTS = {
    "remat": "full",
    "ce_chunk": 512,
    "q_chunk": 512,
    "accum": 1,
    "seq_shard_decode": 0,
    "dtype": "bfloat16",
    "mla_absorb": 0,        # weight-absorbed latent attention
    "moe_ep_only": 0,       # experts: EP over model only (no FSDP gathers)
    "moe_groups": 0,        # shard-local grouped MoE dispatch
    "cache_bf16": 1,        # decode caches in bf16 (0 = match param dtype)
}


def parse_variant(s: str) -> Dict:
    knobs = dict(_KNOB_DEFAULTS)
    if s:
        for kv in s.split(","):
            k, v = kv.split("=")
            knobs[k] = v if k in ("remat", "dtype") else int(v)
    return knobs


def compile_cell(arch: str, shape_name: str, knobs: Dict, multi_pod: bool = False):
    import repro.models.layers as layers_mod
    import repro.models.attention as attn_mod

    # knob injection: chunk sizes are module-level defaults threaded through
    # static args; patch them for this compile only.
    shape = shape_by_name(shape_name)
    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, dtype=knobs["dtype"],
                              mla_absorb=bool(knobs["mla_absorb"]),
                              moe_groups=int(knobs["moe_groups"]))
    mesh = make_production_mesh(multi_pod=multi_pod)
    key = jax.random.PRNGKey(0)
    if knobs["moe_ep_only"]:
        rules.set_moe_ep_only(True)

    old_ce = layers_mod.chunked_cross_entropy.__defaults__
    layers_mod.chunked_cross_entropy.__defaults__ = (
        None, knobs["ce_chunk"], True)
    old_q = attn_mod.gqa_apply.__kwdefaults__["q_chunk"]
    attn_mod.gqa_apply.__kwdefaults__["q_chunk"] = knobs["q_chunk"]
    attn_mod.mla_apply.__kwdefaults__["q_chunk"] = knobs["q_chunk"]

    try:
        params_shape = jax.eval_shape(lambda k: lm.init_params(cfg, k), key)
        psh = rules.to_shardings(rules.param_specs(params_shape, mesh), mesh)

        if shape.kind == "train":
            tc = TrainConfig(remat=knobs["remat"], accum_steps=knobs["accum"])
            opt_shape = jax.eval_shape(lambda p: make_optimizer(tc).init(p), params_shape)
            osh = rules.to_shardings(rules.opt_specs(opt_shape, params_shape, mesh), mesh)
            batch = input_specs(cfg, shape)
            bsh = rules.to_shardings(rules.batch_specs(mesh, batch), mesh)
            fn = make_train_step(cfg, tc)
            jitted = jax.jit(fn, in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, None), donate_argnums=(0, 1))
            args = (_shaped(params_shape, psh), _shaped(opt_shape, osh), _shaped(batch, bsh))
        elif shape.kind == "prefill":
            batch = input_specs(cfg, shape)
            bsh = rules.to_shardings(rules.batch_specs(mesh, batch), mesh)
            jitted = jax.jit(lambda p, b: lm.prefill(cfg, p, b, max_seq=shape.seq_len),
                             in_shardings=(psh, bsh))
            args = (_shaped(params_shape, psh), _shaped(batch, bsh))
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            b = shape.global_batch
            cache_shape = jax.eval_shape(lambda: lm.init_cache(cfg, b, shape.seq_len))
            seq_sharded = b == 1
            cspec = rules.cache_specs(mesh, cache_shape, b, seq_sharded=seq_sharded)
            if knobs["seq_shard_decode"]:
                cspec = _seq_shard_over_model(cspec, cache_shape, mesh)
            csh = rules.to_shardings(cspec, mesh)
            tok = input_specs(cfg, shape)["tokens"]
            tsh = rules.to_shardings(rules.batch_specs(mesh, {"tokens": tok}), mesh)["tokens"]
            jitted = jax.jit(lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos),
                             in_shardings=(psh, csh, tsh, NamedSharding(mesh, P())),
                             out_shardings=(None, csh), donate_argnums=(1,))
            args = (_shaped(params_shape, psh), _shaped(cache_shape, csh),
                    jax.ShapeDtypeStruct(tok.shape, tok.dtype, sharding=tsh),
                    jax.ShapeDtypeStruct((), jnp.int32))

        t0 = time.perf_counter()
        with mesh:
            compiled = jitted.lower(*args).compile()
        dt = time.perf_counter() - t0
    finally:
        layers_mod.chunked_cross_entropy.__defaults__ = old_ce
        attn_mod.gqa_apply.__kwdefaults__["q_chunk"] = old_q
        attn_mod.mla_apply.__kwdefaults__["q_chunk"] = old_q
    return compiled, dt, mesh, cfg


def _shaped(tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), tree, shardings)


def _seq_shard_over_model(cspec, cache_shape, mesh):
    """Shard decode KV-cache SEQ dim over 'model' when heads can't TP-shard."""
    from jax.sharding import PartitionSpec as P

    def fix(spec, leaf):
        if leaf.ndim >= 5 and spec[2] is None and leaf.shape[3] % mesh.shape["model"] == 0 \
                and leaf.shape[3] > 1024:
            lst = list(spec) + [None] * (leaf.ndim - len(spec))
            lst[3] = "model" if lst[3] is None else lst[3]
            return P(*lst)
        return spec

    return jax.tree.map(fix, cspec, cache_shape,
                        is_leaf=lambda x: isinstance(x, P))


def score_traffic_bytes(hlo_text: str, kv_len: int) -> float:
    """Bytes moved through attention-score-shaped tensors (f32, minor dim =
    kv length, rank ≥ 4). The Pallas flash kernel (kernels/attention) keeps
    these in VMEM on TPU, so `memory_s - score_traffic/HBM_BW` is the
    projected TPU memory term with the kernel engaged."""
    import re as _re

    from repro.launch import hlo_analysis as ha

    comps = ha.parse_computations(hlo_text)
    em = _re.search(r"^\s*ENTRY\s+%?([\w.\-]+)", hlo_text, _re.MULTILINE)
    if not em:
        return 0.0
    total = [0.0]

    def trip(cond):
        consts = [int(x) for x in ha._CONST_RE.findall(
            "\n".join(i.rhs for i in comps.get(cond, [])))]
        return max(consts) if consts else 1

    def is_score(type_str):
        m = ha._SHAPE_RE.search(type_str)
        if not m or m.group(1) != "f32":
            return False
        dims = [int(d) for d in m.group(2).split(",") if d]
        return len(dims) >= 4 and dims[-1] == kv_len

    def visit(name, mult, stack=()):
        if name in stack or name not in comps:
            return
        symtab = {i.name: i for i in comps[name]}
        for ins in comps[name]:
            if ins.opcode not in ("parameter", "constant", "get-tuple-element",
                                  "tuple", "bitcast"):
                if is_score(ins.type_str):
                    total[0] += ha._shape_bytes(ins.type_str) * mult
                for op in ins.operands:
                    src = symtab.get(op)
                    if src is not None and is_score(src.type_str):
                        total[0] += ha._shape_bytes(src.type_str) * mult
            if ins.opcode == "while":
                bm = _re.search(r"body=%?([\w.\-]+)", ins.rhs)
                cm = _re.search(r"condition=%?([\w.\-]+)", ins.rhs)
                if bm:
                    visit(bm.group(1), mult * (trip(cm.group(1)) if cm else 1),
                          stack + (name,))

    visit(em.group(1), 1.0)
    return total[0]


def measure(arch: str, shape_name: str, variant: str, multi_pod: bool = False) -> Dict:
    knobs = parse_variant(variant)
    compiled, dt, mesh, cfg = compile_cell(arch, shape_name, knobs, multi_pod)
    hlo = analyze_hlo(compiled.as_text())
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {"temp_gib": ma.temp_size_in_bytes / 2**30,
               "args_gib": ma.argument_size_in_bytes / 2**30}
    except (AttributeError, NotImplementedError):
        pass  # backend exposes no memory stats; anything else should raise
    from benchmarks.roofline import V5E, peaks

    pk = peaks(V5E)

    from repro.configs.base import shape_by_name as _sbn

    kv_len = _sbn(shape_name).seq_len
    score_b = score_traffic_bytes(compiled.as_text(), kv_len)
    res = {
        "arch": arch, "shape": shape_name, "variant": variant or "baseline",
        "knobs": knobs, "compile_s": round(dt, 1),
        "flops_per_device": hlo["flops"],
        "bytes_per_device": hlo["bytes"],
        "collective_bytes_per_device": hlo["collectives"],
        "compute_s": hlo["flops"] / pk["flops"],
        "memory_s": hlo["bytes"] / pk["hbm_bw"],
        "collective_s": hlo["collectives"]["total"] / pk["ici_bw"],
        "score_traffic_s": score_b / pk["hbm_bw"],
        "memory_s_flash": (hlo["bytes"] - score_b) / pk["hbm_bw"],
        **mem,
    }
    res["bound_s"] = max(res["compute_s"], res["memory_s"], res["collective_s"])
    res["bound_s_flash"] = max(res["compute_s"], res["memory_s_flash"], res["collective_s"])
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    res = measure(args.arch, args.shape, args.variant, args.multi_pod)
    print(json.dumps(res, indent=2))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
