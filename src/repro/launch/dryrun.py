import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes; record memory / cost / collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count on first init) — this is the only entry point that fakes 512
host devices; tests and benches see the real single device.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.registry import ASSIGNED, get_config          # noqa: E402
from repro.configs.shapes import SHAPES_BY_NAME, shape_applicable  # noqa: E402
from repro.launch import sharding as shardlib                    # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402
from repro.launch.specs import (arg_shardings, choose_fsdp,      # noqa: E402
                                input_specs, make_step_fn)
from repro.models.registry import build_model                    # noqa: E402
from repro.roofline.collectives import parse_collectives         # noqa: E402


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            *, fsdp=None, seq_parallel=True, vocab_shard=True,
            save_hlo: bool = False, tag: str = "",
            microbatches: int = 1) -> dict:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skipped", "tag": tag}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["reason"] = why
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["n_devices"] = mesh.devices.size
    model = build_model(cfg, param_dtype=jnp.bfloat16)
    rec["microbatches"] = microbatches
    step = make_step_fn(model, shape, microbatches=microbatches)
    args = input_specs(model, shape)
    if fsdp is None:
        fsdp = choose_fsdp(args[0], mesh)
    rec["fsdp"] = bool(fsdp)
    in_sh = arg_shardings(model, shape, mesh, args, fsdp=fsdp)
    # exact per-device resident argument bytes (params + opt + caches) from
    # the actual shardings — memory_analysis double-checks this
    import math as _math
    def _leaf_bytes(l, s):
        shard = s.shard_shape(l.shape)
        return _math.prod(shard) * l.dtype.itemsize
    rec["arg_bytes_per_device"] = int(sum(
        jax.tree.leaves(jax.tree.map(_leaf_bytes, args, in_sh))))
    policy = shardlib.ShardingPolicy(mesh, batch=shape.global_batch,
                                     seq_parallel=seq_parallel,
                                     vocab_shard=vocab_shard)
    # donation: train updates (params, opt) in place; prefill/decode update
    # caches in place — halves resident state exactly like production.
    donate = {"train": (0, 1), "prefill": (2,), "decode": (2,)}[shape.kind]
    # pin output shardings to the input shardings of donated state so the
    # donation actually aliases (mismatched shardings silently drop it)
    if shape.kind == "train":
        out_sh = (in_sh[0], in_sh[1], None)
    elif shape.kind == "prefill":
        out_sh = (None, in_sh[2])
    else:
        out_sh = (None, None, in_sh[2])
    try:
        t0 = time.time()
        with shardlib.use_policy(policy):
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=donate).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:  # CPU backend may not implement it
            rec["memory_analysis"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                    if isinstance(v, (int, float))}
        except Exception as e:
            rec["cost_analysis"] = {"error": str(e)}
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo, mesh.devices.size)
        rec["hlo_bytes"] = len(hlo)
        if save_hlo:
            with open(os.path.join(out_dir, f"{arch}_{shape_name}_"
                                   f"{mesh_name}{tag}.hlo"), "w") as f:
                f.write(hlo)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--no-vocab-shard", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    combos = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = (list(SHAPES_BY_NAME) if (args.all or not args.shape)
              else [args.shape])
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    fsdp = None if args.fsdp is None else (args.fsdp == "on")
    results = []
    for a, s, mp in combos:
        rec = run_one(a, s, mp, args.out, fsdp=fsdp,
                      seq_parallel=not args.no_seq_parallel,
                      vocab_shard=not args.no_vocab_shard,
                      save_hlo=args.save_hlo, tag=args.tag,
                      microbatches=args.microbatches)
        results.append(rec)
        fname = os.path.join(
            args.out, f"{a}_{s}_{'2x16x16' if mp else '16x16'}"
            f"{args.tag}.json")
        with open(fname, "w") as f:
            json.dump(rec, f, indent=1)
        brief = {k: rec.get(k) for k in
                 ("arch", "shape", "mesh", "status", "lower_s", "compile_s",
                  "reason", "error")}
        print(json.dumps(brief), flush=True)
        if rec["status"] == "ok":
            ma = rec.get("memory_analysis", {})
            ca = rec.get("cost_analysis", {})
            print(f"  mem={ma}  flops={ca.get('flops')} "
                  f"bytes={ca.get('bytes accessed')} "
                  f"coll={ {k: round(v['wire_bytes']/1e6,1) for k,v in rec['collectives'].items()} }MB",
                  flush=True)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"DONE ok={n_ok} skipped={n_skip} "
          f"error={len(results) - n_ok - n_skip}")
    return 0 if all(r["status"] in ("ok", "skipped") for r in results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
