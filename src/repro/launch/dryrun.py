import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary code.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config           # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig      # noqa: E402
from repro.launch import hlo_analysis                        # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.models import model as M                          # noqa: E402
from repro.optim.adam import AdamState, AdamW                # noqa: E402
from repro.serve import decode as serve                      # noqa: E402
from repro.sharding.policy import (abstract_params, batch_pspec,  # noqa: E402
                                   sharding_tree)
from repro.train.loop import make_train_step                  # noqa: E402


def _named(mesh, spec):
    return NamedSharding(mesh, spec)


def _batch_sharding(mesh, shape, batch: int):
    """Shard the leading batch dim on dp when divisible, else replicate."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = ("pod", "data") if "pod" in sizes else ("data",)
    dp_n = int(np.prod([sizes[a] for a in dp_axes]))
    dp = batch_pspec(mesh.axis_names)
    ent = [None] * len(shape)
    if shape and shape[0] % dp_n == 0:
        ent[0] = dp
    return _named(mesh, P(*ent))


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *, objective="lm"):
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    B, S = shape.global_batch, shape.seq_len
    f = jax.ShapeDtypeStruct
    if shape.mode in ("train", "prefill"):
        if cfg.family == "audio":
            batch = {"frames": f((B, S, cfg.d_model), jnp.bfloat16)}
            if shape.mode == "train":
                batch["labels"] = f((B, S), jnp.int32)
        else:
            batch = {"tokens": f((B, S), jnp.int32)}
            if cfg.family == "vlm":
                batch["image_embeds"] = f((B, cfg.n_image_tokens, cfg.d_model),
                                          jnp.bfloat16)
        if objective == "apcvfl_distill":
            batch["z_teacher"] = f((B, cfg.d_model), jnp.float32)
            batch["aligned"] = f((B,), jnp.int32)
        return batch
    # decode: one new token against a pre-filled cache
    return {"token": f((B,), jnp.int32), "pos": f((), jnp.int32)}


def _abstract_cache(params_abs, cfg, shape):
    slots = serve.n_cache_slots(cfg, shape)
    B = shape.global_batch
    if cfg.family == "vlm":
        img = jax.ShapeDtypeStruct((B, cfg.n_image_tokens, cfg.d_model),
                                   jnp.bfloat16)
        return jax.eval_shape(
            lambda p, i: M.init_cache(p, cfg, B, slots, i), params_abs, img)
    return jax.eval_shape(lambda p: M.init_cache(p, cfg, B, slots), params_abs)


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool,
                objective: str = "lm", cfg: ModelConfig | None = None):
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.mode == "train" and not cfg.remat:
        # production default: activation checkpointing per block — without it
        # the scanned stack saves every intermediate for backward (TB/device)
        cfg = cfg.with_(remat=True)
    if shape.mode == "decode" and not M.supports_decode(cfg):
        raise SystemExit(f"{arch} is encoder-only: no decode step (skip)")

    mesh = make_production_mesh(multi_pod=multi_pod)
    sch = M.schema(cfg)
    params_abs = abstract_params(sch, jnp.dtype(cfg.dtype))
    pshard = sharding_tree(sch, mesh)

    t0 = time.time()
    with mesh:
        if shape.mode == "train":
            opt = AdamW()
            fns = make_train_step(cfg, opt, objective=objective)
            opt_abs = jax.eval_shape(opt.init, params_abs)
            # opt state m/v mirror the param sharding; step is replicated
            oshard = AdamState(_named(mesh, P()), pshard, pshard)
            batch = input_specs(cfg, shape, objective=objective)
            bshard = {k: _batch_sharding(mesh, v.shape, shape.global_batch)
                      for k, v in batch.items()}
            jitted = jax.jit(fns.step,
                             in_shardings=(pshard, oshard, bshard),
                             out_shardings=(pshard, oshard, None))
            lowered = jitted.lower(params_abs, opt_abs, batch)
        elif shape.mode == "prefill":
            batch = input_specs(cfg, shape)
            bshard = {k: _batch_sharding(mesh, v.shape, shape.global_batch)
                      for k, v in batch.items()}
            fn = lambda p, b: serve.prefill_step(p, cfg, b)
            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(params_abs, batch)
        else:  # decode
            window = serve.decode_window(cfg, shape)
            cache_abs = _abstract_cache(params_abs, cfg, shape)
            cshard = jax.tree.map(lambda s: _named(mesh, s),
                                  serve.cache_pspecs(cache_abs, mesh,
                                                     shape.global_batch))
            step = serve.make_decode_step(cfg, window)
            tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(step, in_shardings=(
                pshard,
                _batch_sharding(mesh, tok.shape, shape.global_batch),
                cshard, _named(mesh, P())))
            lowered = jitted.lower(params_abs, tok, cache_abs, pos)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return cfg, shape, mesh, compiled, t_lower, t_compile


def analyze(arch, shape_name, cfg, compiled, mesh, t_lower, t_compile,
            multi_pod, objective):
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = hlo_analysis.analyze_text(compiled.as_text())
    n_chips = int(np.prod(mesh.devices.shape))
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "objective": objective,
        "n_chips": n_chips,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "params": M.count_params_analytic(cfg),
        "active_params": M.count_active_params(cfg),
        # per-device numbers from the SPMD-partitioned module
        "mem_argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "mem_output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "mem_temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "mem_generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        "xla_flops_per_device_raw": cost.get("flops", 0.0),
        "xla_bytes_per_device_raw": cost.get("bytes accessed", 0.0),
        # loop-corrected (trip-count aware) numbers from the HLO walker
        "hlo_flops_per_device": hlo["flops"],
        "hlo_bytes_per_device": hlo["bytes"],
        "collective_bytes_per_device": hlo["collective_bytes"],
        "collectives": hlo["collectives"],
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--objective", default="lm",
                    choices=["lm", "apcvfl_distill"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", default="", help=(
        "comma list of perf knobs: chunked_attn[:N], seq_par, replicate_kv, "
        "ssd_chunk:N, window:N (see EXPERIMENTS.md section Perf)"))
    args = ap.parse_args()

    cfg = get_config(args.arch)
    for knob in [k for k in args.opt.split(",") if k]:
        name, _, val = knob.partition(":")
        if name == "chunked_attn":
            cfg = cfg.with_(attn_chunk=int(val or 512))
        elif name == "seq_par":
            axes = ("pod", "data", "model") if args.multi_pod else \
                ("data", "model")
            cfg = cfg.with_(seq_parallel=True, mesh_axes=axes)
        elif name == "replicate_kv":
            cfg = cfg.with_(replicate_kv=True)
        elif name == "ssd_chunk":
            cfg = cfg.with_(ssm_chunk=int(val))
        elif name == "ssd_bf16":
            cfg = cfg.with_(ssd_bf16=True)
        elif name == "softmax_bf16":
            cfg = cfg.with_(softmax_bf16=True)
        elif name == "window":
            cfg = cfg.with_(sliding_window=int(val))
        else:
            raise SystemExit(f"unknown opt {name}")

    cfg, shape, mesh, compiled, t_lower, t_compile = lower_combo(
        args.arch, args.shape, multi_pod=args.multi_pod,
        objective=args.objective, cfg=cfg)
    print(compiled.memory_analysis())
    print({k: v for k, v in compiled.cost_analysis().items()
           if k in ("flops", "bytes accessed")})
    rec = analyze(args.arch, args.shape, cfg, compiled, mesh, t_lower,
                  t_compile, args.multi_pod, args.objective)
    rec["opt"] = args.opt
    os.makedirs(args.out, exist_ok=True)
    tag = (args.tag + "_") if args.tag else ""
    name = f"{tag}{args.arch}_{args.shape}_{rec['mesh']}"
    if args.objective != "lm":
        name += "_" + args.objective
    path = os.path.join(args.out, name + ".json")
    with open(path, "w") as fh:
        json.dump(rec, fh, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
