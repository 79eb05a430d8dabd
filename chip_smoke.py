#!/usr/bin/env python3
"""Drive the system's main path once on a TPU, in one process.

    python chip_smoke.py                # one chip: the whole main path
    python chip_smoke.py --four-chips   # four chips: sharded training only

One chip, in order, one summary line per phase:

1. device gate: JAX's default backend must be a TPU (there is no CPU path);
2. calibrate: ``--zoo`` over the default study battery, through
   :func:`repro.profiles.cli.main`, into a fresh measurement cache;
3. fit agreement: ``ovl_flop_mem`` refitted on the chip's train split, once
   on the TPU and once on the host CPU, must predict every battery row
   alike to rtol 1e-3, with each parameter's difference alone moving no
   prediction further;
4. kernels: every :mod:`repro.kernels.ops` wrapper compiled at real widths
   against :mod:`repro.kernels.ref`, then priced by ``PerfSession`` from the
   chip's profile and timed (predicted vs measured is information only);
5. serve: :func:`repro.serving.cli.main` ``--smoke``, a 64-request HTTP
   burst priced from the chip's profile with zero kernel timings;
6. train: three xlstm-125m steps at published width through
   :func:`repro.launch.train.main`.

``--four-chips`` runs only the comparison the four-chip path exists for:
three xlstm-125m steps on one chip, then the same seed and global batch on
a (data 2, model 2) mesh over four chips.

Every phase that fails exits non-zero before the last line.  The last line
of stdout is ``{"ok": true, "device": {...}}`` naming the device as JAX
reports it.  Artifacts go under ``runs/chip_smoke/``; compiled programs go
to the persistent compilation cache (:mod:`repro.compile_cache`).
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import math
import re
import shutil
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
RUN_DIR = ROOT / "runs" / "chip_smoke"
SEED = 0
ARCH = "xlstm-125m"
TRAIN_STEPS, TRAIN_SEQ, TRAIN_BATCH = 3, 2048, 8
TRIALS = 8
FIT_RTOL = 1e-3
LOSS_RTOL = 2e-2
# max |kernel - ref| over max |ref|: a bf16 output rounds at 2^-8 relative;
# an f32 contraction at one bf16 MXU pass would miss the f32 limit
TOL = {jnp.dtype(jnp.bfloat16): 3e-2, jnp.dtype(jnp.float32): 1e-4}


def fail(phase: str, why: str):
    raise SystemExit(f"chip_smoke: {phase} FAILED: {why}")


def say(phase: str, line: str) -> None:
    print(f"[{phase}] {line}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_gate(count: int):
    """The chip JAX runs on, or exit: no phase may fall back to the CPU."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail("device", f"JAX found no backend: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail("device", f"JAX's default backend is {dev.platform!r}, not a "
                       f"TPU; this script has no CPU path")
    if len(devices) < count:
        fail("device", f"{count} chips needed, JAX sees {len(devices)}")
    say("device", f"platform={dev.platform} kind={dev.device_kind} "
                  f"count={len(devices)}")
    return devices


def calibrate(run_dir: Path, tags, trials: int = TRIALS) -> Path:
    from repro.profiles import cli as profiles_cli
    from repro.profiles.profile import load_profile
    from repro.studies import MODEL_ZOO
    from repro.studies.study import gmre_of, profile_accuracy

    out = run_dir / "profile.json"
    t0 = time.perf_counter()
    rc = profiles_cli.main(
        ["--zoo", "--tags", *tags, "--trials", str(trials),
         "--cache-dir", str(run_dir / "measurements"), "--out", str(out)])
    if rc != 0:
        fail("calibrate", f"repro.calibrate exited {rc}")
    profile = load_profile(out)
    fp, dev = profile.fingerprint, jax.devices()[0]
    if (fp.platform, fp.device_kind) != (dev.platform, dev.device_kind):
        fail("calibrate", f"profile fingerprint {fp.id} is not this "
                          f"{dev.platform} {dev.device_kind}")
    for entry in MODEL_ZOO:
        fit = profile.fits[entry.name].fit
        if not fit.converged:
            fail("calibrate", f"{entry.name} did not converge: {fit}")
        # a rung may leave shape parameters free; its costs are >= 0
        if not all(math.isfinite(v) for v in fit.params.values()) \
                or min(fit.params[k] for k in entry.nonneg) < 0:
            fail("calibrate", f"{entry.name} params not finite and "
                              f"non-negative: {fit.params}")
    gmre = {name: gmre_of(errs)
            for name, errs in profile_accuracy(profile).items()}
    say("calibrate", f"converged on {fp.id} ({fp.device_kind}): "
                     f"{len(profile.kernel_names)} kernels, "
                     f"{time.perf_counter() - t0:.1f} s; held-out gmre "
        + " ".join(f"{n}={g * 100:.2f}%" for n, g in gmre.items()))
    return out


def battery_table(run_dir: Path, profile_path: Path, tags,
                  trials: int = TRIALS):
    """The calibration's feature table, served by its measurement cache
    (zero timings), and the zoo's models."""
    from repro.core.uipick import (
        ALL_GENERATORS, CountingTimer, KernelCollection, MatchCondition,
        gather_feature_table,
    )
    from repro.profiles.cache import MeasurementCache
    from repro.profiles.presets import DEFAULT_OUTPUT_FEATURE
    from repro.profiles.profile import load_profile
    from repro.studies import MODEL_ZOO

    profile = load_profile(profile_path)
    models = {e.name: e.model() for e in MODEL_ZOO}
    features = [DEFAULT_OUTPUT_FEATURE] + sorted(
        {f for m in models.values() for f in m.feature_names})
    kernels = KernelCollection(ALL_GENERATORS).generate_kernels(
        list(tags), generator_match_cond=MatchCondition.INTERSECT)
    timer = CountingTimer()
    table = gather_feature_table(
        features, kernels, trials=trials, timer=timer,
        cache=MeasurementCache(run_dir / "measurements",
                               profile.fingerprint))
    if timer.calls:
        fail("fit", f"{timer.calls} kernels were re-timed; the table must "
                    f"come from the calibration's cache")
    return table, models


def refit(models, train, device) -> dict:
    """``ovl_flop_mem``'s params, the zoo refitted on ``device``."""
    from repro.core.calibrate import fit_models
    from repro.studies import MODEL_ZOO

    with jax.default_device(device):
        return fit_models(models, train, nonneg={
            e.name: e.nonneg for e in MODEL_ZOO})["ovl_flop_mem"].params


def prediction_shift(model, params, other, table) -> dict:
    """Largest relative move of any row's prediction when ``other``'s
    value of each parameter, alone and then all at once, replaces
    ``params``'s."""
    base = _predict(model, params, table)
    moved = {k: _max_rel(_predict(model, {**params, k: other[k]}, table),
                         base) for k in params}
    moved["all"] = _max_rel(_predict(model, other, table), base)
    return moved


def fit_agreement(run_dir: Path, profile_path: Path, tags):
    """Refit the zoo on the chip's own train split on the TPU and on the
    host CPU; returns ``ovl_flop_mem`` and both of its fits."""
    from repro.core.uipick import holdout_split

    table, models = battery_table(run_dir, profile_path, tags)
    train, held = holdout_split(table)
    ovl = models["ovl_flop_mem"]
    on_tpu = refit(models, train, jax.devices()[0])
    on_cpu = refit(models, train, jax.devices("cpu")[0])
    # f32 LM resolves a parameter only as far as it moves the fit: one the
    # battery barely sees (p_edge; p_madd under ~0.6 ms of dispatch per
    # row) may rest anywhere along a flat direction.  So agreement is
    # judged where the battery determines the fit, on every battery row
    # (train and held out); the kernels phase prints how far the two fits
    # part at real widths, where such a parameter prices the work
    moved = prediction_shift(ovl, on_tpu, on_cpu, table)
    raw = {k: _max_rel(on_tpu[k], on_cpu[k]) for k in on_tpu}
    if max(moved.values()) > FIT_RTOL:
        fail("fit", f"ovl_flop_mem tpu vs cpu moves predictions by "
                    f"{moved} > {FIT_RTOL}: tpu={on_tpu} cpu={on_cpu}")
    say("fit", f"ovl_flop_mem tpu vs cpu over {len(table)} battery rows "
               f"({len(train)} train, {len(held)} held out): predictions "
               f"differ by at most {max(moved.values()):.3g} (limit "
               f"{FIT_RTOL}; per parameter and all: "
               + ", ".join(f"{k} {v:.3g}" for k, v in moved.items())
               + "); raw parameter rtol "
               + ", ".join(f"{k} {v:.3g}" for k, v in raw.items())
               + f"; tpu={on_tpu} cpu={on_cpu}")
    return ovl, on_tpu, on_cpu


def _max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def _predict(model, params, table):
    """Per-row predictions of ``model`` under ``params``, on the host."""
    with jax.default_device(jax.devices("cpu")[0]):
        p = jnp.asarray([params[n] for n in model.param_names])
        return np.asarray(model.batched_eval(
            p, jnp.asarray(model.align(table, missing="zero"))))


def kernel_cases(key):
    """(name, wrapper, args, reference) at widths of configs the repo
    carries, inputs drawn from ``key`` on the device."""
    from repro.kernels import ops, ref

    draws = itertools.count()
    bf, f32 = jnp.bfloat16, jnp.float32

    def rn(*shape, dtype=f32, scale=1.0):
        sub = jax.random.fold_in(key, next(draws))
        return (jax.random.normal(sub, shape, f32) * scale).astype(dtype)

    # yi-6b attention: 32 q heads, 4 kv heads, head_dim 128, seq 4096
    q = rn(1, 4096, 32, 128, dtype=bf)
    k, v = rn(1, 4096, 4, 128, dtype=bf), rn(1, 4096, 4, 128, dtype=bf)
    # zamba2-7b SSM: d_inner 7168 = 112 heads of 64, d_state 64, chunk 256
    xdt = rn(1, 4096, 112, 64, dtype=bf)
    da = -jnp.abs(rn(1, 4096, 112)) * 0.1
    b_ssm, c_ssm = rn(1, 4096, 112, 64, dtype=bf), rn(1, 4096, 112, 64,
                                                        dtype=bf)
    # xlstm-125m sLSTM: 4 heads × 192, seq 2048, the train cell's batch 8
    g_in = rn(8, 2048, 4, 4, 192, scale=0.5)
    r_gates, b_gates = rn(4, 192, 4, 192, scale=0.1), rn(4, 4, 192,
                                                         scale=0.1)
    # the study battery's largest stream / madd / dg sizes
    streams = [rn(1 << 24) for _ in range(4)]
    return [
        ("matmul", functools.partial(ops.matmul, block_m=512, block_n=512,
                                     block_k=512),
         (rn(4096, 4096, dtype=bf), rn(4096, 4096, dtype=bf)),
         ref.matmul_ref),
        ("flash_attention", functools.partial(ops.flash_attention,
                                              causal=True),
         (q, k, v), functools.partial(ref.attention_ref, causal=True)),
        ("mamba2_ssd", functools.partial(ops.mamba2_ssd, chunk=256),
         (xdt, da, b_ssm, c_ssm), ref.ssd_ref),
        ("stencil5", ops.stencil5, (rn(4096, 4096),), ref.stencil5_ref),
        ("slstm_cell", ops.slstm_cell, (g_in, r_gates, b_gates),
         ref.slstm_cell_ref),
        ("stream_strided", functools.partial(ops.stream_strided, stride=2),
         (streams,), functools.partial(ref.stream_ref, block=1024,
                                       stride=2)),
        ("madd_throughput", functools.partial(ops.madd_throughput,
                                              iters=512),
         (rn(65536),), functools.partial(ref.madd_ref, iters=512)),
        ("dg_diff", ops.dg_diff, (rn(3, 64, 64), rn(64, 65536)),
         ref.dg_diff_ref),
    ]


def _median_seconds(fn, args, calls: int = 10) -> float:
    jax.block_until_ready(fn(*args))           # warm-up (compiled above)
    ts = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def kernels(profile_path: Path, cases, fits) -> None:
    """``fits`` is ``fit_agreement``'s model with its TPU and CPU fits."""
    from repro.api import PerfSession

    model, on_tpu, on_cpu = fits
    session = PerfSession.open(profile_path)
    parted = {}
    for name, fn, args, reference in cases:
        out = jax.block_until_ready(fn(*args))
        # the references run f32 contractions at full precision: a TPU's
        # default would round their operands to bf16
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*args)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want.astype(jnp.float32)))
                    / jnp.maximum(jnp.max(jnp.abs(want.astype(jnp.float32))),
                                  1e-30))
        tol = TOL[jnp.dtype(out.dtype)]
        if out.shape != want.shape or not err <= tol:
            fail("kernels", f"{name}: shape {out.shape} vs {want.shape}, "
                            f"max error {err:.3g} > {tol}")
        del want
        pred = session.predict(fn, *args, name=name)
        host = _predict(model, on_cpu, [pred.features])[0]
        parted[name] = _max_rel(host, _predict(model, on_tpu,
                                               [pred.features])[0])
        measured = _median_seconds(fn, args)
        say("kernels", f"{name}: {out.dtype} {tuple(out.shape)} matches "
                       f"ref (max err {err:.2e} <= {tol}); predicted "
                       f"{pred.seconds * 1e6:.1f} us (host refit "
                       f"{host * 1e6:.1f} us), measured "
                       f"{measured * 1e6:.1f} us (median of 10), "
                       f"{len(pred.unmodeled)} unmodeled features")
    if session.timer.calls:
        fail("kernels", f"pricing timed {session.timer.calls} kernels")
    say("kernels", "host refit vs TPU refit at real widths (information; "
                   "the battery does not pin every rate they price): "
        + ", ".join(f"{n} {v:.3g}" for n, v in parted.items()))


def serve(profile_path: Path) -> None:
    from repro.serving import cli as serving_cli

    rc = serving_cli.main(["--profile", str(profile_path), "--smoke",
                           "--burst", "64", "--expect-zero-timings"])
    if rc != 0:
        fail("serve", f"repro.serve --smoke exited {rc}")
    say("serve", "64-request burst answered from the chip's profile, "
                 "0 kernel timings")


def train(run_dir: Path) -> None:
    from repro.configs import SHAPES_BY_NAME
    from repro.launch import train as launcher

    ckpt = run_dir / "train_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)    # a stale step would resume
    full = SHAPES_BY_NAME["train_4k"]
    say("train", f"{ARCH} at published widths; cut: global batch "
                 f"{TRAIN_BATCH} x seq {TRAIN_SEQ} in place of train_4k's "
                 f"{full.global_batch} x {full.seq_len}")
    rc = launcher.main(["--arch", ARCH, "--steps", str(TRAIN_STEPS),
                        "--seq-len", str(TRAIN_SEQ),
                        "--batch", str(TRAIN_BATCH), "--ckpt-dir", str(ckpt)])
    if rc != 0:
        fail("train", f"repro.launch.train exited {rc} (non-finite loss or "
                      f"replayed steps)")
    say("train", f"{TRAIN_STEPS} finite steps, 0 replayed")


def _state_bytes(state) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(
        (state.params, state.opt_state)))


def four_chips(run, devices) -> None:
    """The same seed and global batch on one chip and on a (data 2,
    model 2) mesh: equal losses, state split across the chips, and the
    collectives the sharded step needs."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import Trainer

    def losses(trainer):
        if any(row.get("event") == "restored" for row in trainer.metrics_log):
            fail("four", f"steps were replayed: {trainer.metrics_log}")
        return [row["loss"] for row in trainer.metrics_log if "loss" in row]

    def walls(trainer):
        return [round(row["wall_s"], 4) for row in trainer.metrics_log
                if "wall_s" in row]

    single = Trainer(run, mesh=make_host_mesh(devices=devices[:1]))
    state = single.train(single.init_state(run.seed), TRAIN_STEPS,
                         log_every=0)
    one, one_walls = losses(single), walls(single)
    del state, single

    sharded = Trainer(run, mesh=make_host_mesh(model=2, devices=devices))
    state = sharded.init_state(run.seed)
    total = _state_bytes(state)
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    # (data 2, model 2): FSDP and tensor parallelism give each chip about
    # a quarter of the state; all of it on one chip would be `total`
    if max(in_use) > 0.5 * total or min(in_use) == 0:
        fail("four", f"state not split: bytes_in_use {in_use} for "
                     f"{total} bytes of params + optimizer state")
    ops = re.findall(r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start)?\(",
                     sharded.step_hlo(state))
    colls = {c: ops.count(c) for c in sorted(set(ops))}
    if not colls.get("all-reduce", 0) + colls.get("reduce-scatter", 0) \
            or not colls.get("all-gather"):
        fail("four", f"sharded step lacks gradient reduction or parameter "
                     f"gathers: {colls}")
    state = sharded.train(state, TRAIN_STEPS, log_every=0)
    four = losses(sharded)
    rel = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    if len(four) != TRAIN_STEPS or len(one) != TRAIN_STEPS \
            or not all(math.isfinite(x) for x in one + four) \
            or max(rel) > LOSS_RTOL:
        fail("four", f"losses disagree: one chip {one}, four chips {four}")
    say("four", f"losses one chip {one} vs (data 2, model 2) {four}: max "
                f"rel {max(rel):.3g} (limit {LOSS_RTOL})")
    say("four", f"bytes_in_use per chip {in_use} for {total} bytes of "
                f"state; step collectives {colls}")
    say("four", f"step wall s (first compiles): one chip {one_walls}, "
                f"four chips {walls(sharded)}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded xlstm-125m training on four "
                         "chips against one chip")
    args = ap.parse_args(argv)

    count = 4 if args.four_chips else 1
    # the fit-agreement phase refits on the host: keep the CPU backend
    # beside the chip where the platform list is pinned
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", f"{platforms},cpu")
    devices = device_gate(count)
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache, spans
    from repro.studies import STUDY_TAGS

    say("cache", f"compilation cache at {compile_cache.enable()}")
    RUN_DIR.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    if args.four_chips:
        from repro.launch.train import run_config
        four_chips(run_config(ARCH, steps=TRAIN_STEPS, seq_len=TRAIN_SEQ,
                              batch=TRAIN_BATCH,
                              ckpt_dir=str(RUN_DIR / "four_ckpt")),
                   devices[:count])
    else:
        shutil.rmtree(RUN_DIR / "measurements", ignore_errors=True)
        profile = calibrate(RUN_DIR, STUDY_TAGS)
        fits = fit_agreement(RUN_DIR, profile, STUDY_TAGS)
        kernels(profile, kernel_cases(jax.random.PRNGKey(SEED)), fits)
        serve(profile)
        train(RUN_DIR)
    # every compile since repro.spans was imported: charged to a span, or
    # to none (a thread with no open span)
    alone = spans.unowned()
    comp = {k: v + sum(t.get(k, 0) for t in spans.totals().values())
            for k, v in alone.items()}
    say("compile", f"{comp['compile_s']:.1f} s backend compile over "
                   f"{comp['compiles']} compiles ({alone['compiles']} "
                   f"outside any span); persistent cache "
                   f"{comp['cache_hits']} hits, {comp['cache_misses']} "
                   f"written; wall {time.perf_counter() - t0:.1f} s")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
