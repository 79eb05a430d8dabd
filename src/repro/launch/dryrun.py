"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. resolves logical-axis shardings for params / optimizer / cache / batch,
  3. ``jit(step).lower(**ShapeDtypeStructs).compile()`` — no allocation,
  4. prints ``compiled.memory_analysis()`` (proves the cell fits HBM) and
     ``compiled.cost_analysis()`` (FLOPs / bytes for §Roofline),
  5. saves the optimized HLO (zstd) for the trip-count-aware cost walker in
     ``repro.core.hlo`` (XLA's cost_analysis visits loop bodies once, so the
     roofline pass re-derives FLOPs/bytes/collectives itself), and
  6. writes a JSON record consumed by EXPERIMENTS.md §Dry-run/§Roofline.

It is a CPU rehearsal: ``main()`` pins the CPU platform and asks XLA for
512 virtual host devices, so the dry-run never takes an attached chip.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k \
      --mesh single --out runs/dryrun
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES_BY_NAME, get_config, shapes_for
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.launch.presets import make_run_config
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import lm
from repro.optim import adamw
from repro.sharding import logical_to_pspec, tree_shardings, use_mesh
from repro.sharding.axes import RULE_PRESETS


def _shard_tree(axes_tree, spec_tree, mesh):
    return tree_shardings(axes_tree, spec_tree, mesh=mesh)


def build_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Returns (fn, arg_specs, in_shardings, out_shardings)."""
    run = make_run_config(arch, shape_name, overrides=overrides)
    cfg, shape = run.model, run.shape
    rep = NamedSharding(mesh, P())

    params_abs = lm.abstract_params(cfg)
    params_sh = _shard_tree(lm.param_axes(cfg), params_abs, mesh)

    if shape.kind == "train":
        fn = make_train_step(run)
        opt_abs = adamw.abstract_opt_state(params_abs, run.optimizer)
        opt_sh = adamw.opt_state_axes(params_sh)._replace(count=rep)
        batch_abs = S.train_batch_specs(cfg, shape)
        batch_sh = _shard_tree(S.batch_axes(cfg), batch_abs, mesh)
        args = (params_abs, opt_abs, batch_abs)
        in_sh = (params_sh, opt_sh, batch_sh)
        out_sh = (params_sh, opt_sh, rep)
    elif shape.kind == "prefill":
        fn = make_prefill_step(run)
        cache_abs = lm.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cache_sh = _shard_tree(
            lm.cache_axes(cfg, shape.global_batch, shape.seq_len),
            cache_abs, mesh)
        batch_abs = S.prefill_specs(cfg, shape)
        batch_sh = _shard_tree(
            {k: v for k, v in S.batch_axes(cfg).items() if k in batch_abs},
            batch_abs, mesh)
        logits_sh = NamedSharding(mesh, logical_to_pspec(
            ("batch", "seq", "vocab"), mesh,
            dim_sizes=(shape.global_batch, 1, lm.padded_vocab(cfg))))
        args = (params_abs, cache_abs, batch_abs)
        in_sh = (params_sh, cache_sh, batch_sh)
        out_sh = (cache_sh, logits_sh)
    else:  # decode
        fn = make_decode_step(run)
        cache_abs = lm.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        cache_sh = _shard_tree(
            lm.cache_axes(cfg, shape.global_batch, shape.seq_len),
            cache_abs, mesh)
        tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_sh = NamedSharding(mesh, logical_to_pspec(
            ("batch", "seq"), mesh, dim_sizes=(shape.global_batch, 1)))
        cur_abs = jax.ShapeDtypeStruct((), jnp.int32)
        logits_sh = NamedSharding(mesh, logical_to_pspec(
            ("batch", "seq", "vocab"), mesh,
            dim_sizes=(shape.global_batch, 1, lm.padded_vocab(cfg))))
        args = (params_abs, cache_abs, tok_abs, cur_abs)
        in_sh = (params_sh, cache_sh, tok_sh, rep)
        out_sh = (cache_sh, logits_sh)
    return fn, args, in_sh, out_sh


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             overrides=None, save_hlo: bool = True):
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "status": "start",
        "overrides": overrides or {},
    }
    t0 = time.time()
    try:
        preset = (overrides or {}).get("sharding_preset", "tp_fsdp")
        with use_mesh(mesh, RULE_PRESETS[preset]):
            fn, args, in_sh, out_sh = build_cell(arch, shape_name, mesh,
                                                 overrides)
            jf = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jf.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        print(ma)
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
        mem["total_per_device_bytes"] = (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem["alias_bytes"])
        rec["memory"] = mem
        ca = compiled.cost_analysis() or {}
        print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        rec["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        if save_hlo:
            txt = compiled.as_text()
            try:
                import zstandard as zstd
            except ModuleNotFoundError:  # optional dep: save uncompressed
                hlo_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}.hlo"
                hlo_path.write_text(txt)
            else:
                hlo_path = out_dir / \
                    f"{arch}__{shape_name}__{mesh_kind}.hlo.zst"
                hlo_path.write_bytes(zstd.ZstdCompressor(level=3).compress(
                    txt.encode()))
            rec["hlo_path"] = str(hlo_path)
            rec["hlo_chars"] = len(txt)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}.json"
    out_path.write_text(json.dumps(rec, indent=2))
    print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: {rec['status']} "
          f"({rec['total_s']}s)")
    return rec


def main():
    # before the first backend query: virtual devices, and never the chip
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512"]))
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "pod2"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="key=value run-config overrides (repeatable)")
    args = ap.parse_args()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    rec = run_cell(args.arch, args.shape, args.mesh, Path(args.out),
                   overrides=overrides or None, save_hlo=not args.no_hlo)
    raise SystemExit(0 if rec["status"] == "ok" else 1)


if __name__ == "__main__":
    main()
