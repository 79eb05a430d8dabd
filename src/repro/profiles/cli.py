"""``python -m repro.calibrate`` — the once-per-machine calibration CLI.

Default command: wires the whole pipeline — UIPiCK filter tags →
measurement-kernel generation → feature gathering (through the
content-addressed measurement cache) → Levenberg-Marquardt fit → atomic
profile save.  A warm rerun with the same cache directory performs ZERO
kernel timings (every kernel hits the cache) and writes a byte-identical
profile; ``--expect-zero-timings`` turns that guarantee into an exit code
for CI.  ``--zoo`` fits the whole model-zoo scope ladder over one battery
with a held-out split (the cross-machine study artifact); ``--synthetic``
runs against a synthetic ground-truth device instead of real hardware.

Subcommands:

    predict  profile + UIPiCK tags → per-kernel runtime predictions with
             the cost-explanatory per-term breakdown; ZERO kernel
             timings, one jit-compiled batched model evaluation
    compare  ≥2 profiles → per-model × per-variant held-out relative-error
             report (markdown + JSON); machines must be distinct;
             ``--sweep`` adds the per-zoo-rank accuracy/scope curve
    merge    same-machine profiles → one profile (union of fits; conflicts
             are errors); with --fleet, cross-machine → fleet bundle
    gc       evict measurement-cache entries (foreign fingerprint,
             corrupt, or older than --max-age)

Examples:

    # full battery, persistent cache, profile artifact
    python -m repro.calibrate --out machine_profile.json \
        --cache-dir ~/.cache/repro-measurements --trials 8

    # predict + explain runtimes from a saved profile (no measuring)
    python -m repro.calibrate predict machine_profile.json \
        --tags matmul_sq dtype:float32 --model ovl_flop_mem --explain 3

    # cross-machine study on two synthetic devices, then compare
    python -m repro.calibrate --zoo --synthetic apex --out a.json
    python -m repro.calibrate --zoo --synthetic bulk --out b.json
    python -m repro.calibrate compare a.json b.json --report report.md \
        --sweep
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

from repro import spans
from repro.core.calibrate import fit_model
from repro.core.model import Model
from repro.core.uipick import (
    ALL_GENERATORS,
    CountingTimer,
    KernelCollection,
    MatchCondition,
    gather_feature_table,
)
from repro.profiles.cache import MeasurementCache
from repro.profiles.fingerprint import DeviceFingerprint
from repro.profiles.presets import (
    BASE_MODEL_EXPR,
    CALIBRATION_TAGS,
    DEFAULT_OUTPUT_FEATURE,
    SMOKE_MODEL_EXPR,
    SMOKE_TAGS,
)
from repro.profiles.profile import (
    MachineProfile,
    ModelFit,
    ProfileError,
    save_profile,
)

_MATCH = {c.name.lower(): c for c in MatchCondition}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate",
        description="Calibrate this machine's black-box cost model and "
                    "save a reusable profile.  Subcommands: compare, "
                    "merge, gc (see module docstring).")
    ap.add_argument("--out", default="machine_profile.json",
                    help="profile JSON destination (atomic write)")
    ap.add_argument("--cache-dir", default=None,
                    help="content-addressed measurement cache directory; "
                         "warm reruns perform zero timings")
    ap.add_argument("--tags", nargs="+", default=None,
                    help="UIPiCK filter tags (default: the full "
                         "calibration battery)")
    ap.add_argument("--match", choices=sorted(_MATCH), default="intersect",
                    help="generator tag match condition (paper §7.1)")
    ap.add_argument("--expr", default=None,
                    help="model expression to calibrate "
                         "(default: the base linear model)")
    ap.add_argument("--output-feature", default=DEFAULT_OUTPUT_FEATURE,
                    help="measured output feature id")
    ap.add_argument("--name", default="base",
                    help="name of the fit inside the profile")
    ap.add_argument("--trials", type=int, default=8,
                    help="timing trials per measurement kernel")
    ap.add_argument("--smoke", action="store_true",
                    help="use the tiny smoke battery + 2-parameter model "
                         "(CI-sized)")
    ap.add_argument("--zoo", action="store_true",
                    help="fit the whole model zoo over one battery with a "
                         "held-out split (cross-machine study artifact)")
    ap.add_argument("--holdout-fraction", type=float, default=0.25,
                    help="held-out fraction of the battery (with --zoo)")
    ap.add_argument("--synthetic", default=None, metavar="DEVICE",
                    help="calibrate a synthetic ground-truth device "
                         "(apex/bulk/citra) instead of real hardware")
    ap.add_argument("--synthetic-noise", type=float, default=0.0,
                    help="relative timing noise of the synthetic device")
    ap.add_argument("--expect-zero-timings", action="store_true",
                    help="exit 1 unless every kernel came from the cache "
                         "(no timing passes ran)")
    ap.add_argument("--retime-rel-std", type=float, default=None,
                    metavar="FRACTION",
                    help="re-time battery rows whose relative wall-clock "
                         "std exceeds this threshold (noisy-row "
                         "re-measurement heuristic)")
    ap.add_argument("--force", action="store_true",
                    help="with --zoo: fit even when the static "
                         "identifiability analysis finds zoo rungs the "
                         "battery cannot determine (their fitted values "
                         "are arbitrary along the null space)")
    return ap


def _retime_line(args, retimed) -> None:
    if args.retime_rel_std is not None:
        print(f"[calibrate] retimed={len(retimed)} rows above "
              f"rel-std {args.retime_rel_std:g}"
              + (f": {sorted(retimed)}" if retimed else ""))


def _noise_line(table) -> str:
    s = table.noise_summary()
    if not s:
        return "wall-clock noise: n/a (no spread metadata)"
    return (f"wall-clock noise: max rel std {s['max_rel_std'] * 100:.2f}% "
            f"median {s['median_rel_std'] * 100:.2f}% "
            f"over {int(s['rows'])} rows")


def _calibrate(argv: Optional[List[str]]) -> int:
    """One calibration: the root span ``calibrate.profile`` (attrs
    ``trials``, ``kernels``) around everything it does."""
    with spans.span("calibrate.profile") as root:
        return _calibrate_in(argv, root.attrs)


def _calibrate_in(argv: Optional[List[str]], attrs) -> int:
    args = build_parser().parse_args(argv)
    attrs["trials"] = args.trials

    if args.synthetic:
        from repro.testing.synthdev import fleet_device
        try:
            device = fleet_device(args.synthetic,
                                  noise=args.synthetic_noise,
                                  output_feature=args.output_feature)
        except (KeyError, ValueError) as e:
            print(f"[calibrate] {e.args[0]}", file=sys.stderr)
            return 2
        fingerprint = device.fingerprint
        base_timer = device.timer
    else:
        fingerprint = DeviceFingerprint.local()
        base_timer = None

    cache = MeasurementCache(args.cache_dir, fingerprint) \
        if args.cache_dir else None
    timer = CountingTimer(base_timer) if base_timer else CountingTimer()
    # amortized symbolic counting: battery counts come from kernel-family
    # polynomials (persisted beside the measurement cache) instead of one
    # jaxpr trace per kernel per size
    from repro.core.countengine import CountEngine
    engine = CountEngine(
        store=cache.count_store if cache is not None else None)

    if args.zoo:
        from repro.studies import (
            MODEL_ZOO, STUDY_SMOKE_TAGS, STUDY_TAGS, StudyError, run_study,
        )
        tags = args.tags or (STUDY_SMOKE_TAGS if args.smoke else STUDY_TAGS)
        print(f"[calibrate] device={fingerprint.id} zoo="
              f"{[e.name for e in MODEL_ZOO]} trials={args.trials} "
              f"cache={args.cache_dir or 'off'}")
        try:
            profile = run_study(
                fingerprint=fingerprint, timer=timer, cache=cache,
                tags=tags, output_feature=args.output_feature,
                trials=args.trials,
                holdout_fraction=args.holdout_fraction,
                match=_MATCH[args.match],
                retime_rel_std=args.retime_rel_std,
                engine=engine, force=args.force)
        except StudyError as e:
            print(f"[calibrate] {e}", file=sys.stderr)
            return 2
        attrs["kernels"] = len(profile.kernel_names)
        with spans.span("calibrate.save"):
            save_profile(profile, args.out)
        _retime_line(args, profile.retimed_rows)
        print(f"[calibrate] {_noise_line(profile.holdout)}")
        for name, mf in sorted(profile.fits.items()):
            print(f"[calibrate] fit {name}: residual="
                  f"{mf.fit.residual_norm:.3g} converged="
                  f"{mf.fit.converged} params={mf.params}")
    else:
        expr = args.expr or (SMOKE_MODEL_EXPR if args.smoke
                             else BASE_MODEL_EXPR)
        tags = args.tags or (SMOKE_TAGS if args.smoke else CALIBRATION_TAGS)
        model = Model(args.output_feature, expr)
        with spans.span("calibrate.battery"):
            kernels = KernelCollection(ALL_GENERATORS).generate_kernels(
                tags, generator_match_cond=_MATCH[args.match])
        attrs["kernels"] = len(kernels)
        if not kernels:
            print(f"no measurement kernels match tags {tags!r}",
                  file=sys.stderr)
            return 2
        print(f"[calibrate] device={fingerprint.id} kernels={len(kernels)} "
              f"trials={args.trials} cache={args.cache_dir or 'off'}")
        table = gather_feature_table(model.all_features(), kernels,
                                     trials=args.trials, timer=timer,
                                     cache=cache,
                                     retime_rel_std=args.retime_rel_std,
                                     engine=engine)
        _retime_line(args, table.retimed_rows)
        fit = fit_model(model, table, nonneg=True)
        profile = MachineProfile(
            fingerprint=fingerprint,
            fits={args.name: ModelFit.from_fit(model, fit)},
            trials=args.trials,
            kernel_names=[k.name for k in kernels])
        with spans.span("calibrate.save"):
            save_profile(profile, args.out)
        print(f"[calibrate] {_noise_line(table)}")
        print(f"[calibrate] fit residual={fit.residual_norm:.3g} "
              f"converged={fit.converged} params={fit.params}")

    hits = cache.hits if cache is not None else 0
    print(f"[calibrate] timings_performed={timer.calls} cache_hits={hits}")
    print(f"[calibrate] count_traces={engine.trace_count} "
          f"count_hits={engine.hits}")
    print(f"[calibrate] profile -> {args.out}")
    if args.expect_zero_timings and timer.calls:
        print(f"[calibrate] FAIL: expected a fully warm cache but "
              f"{timer.calls} kernels were timed", file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# Subcommands
# ---------------------------------------------------------------------------


def _cmd_predict(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate predict",
        description="Predict (and explain) kernel runtimes from a saved "
                    "machine profile: UIPiCK tags select the kernels, "
                    "features come from the jaxpr counter (or the "
                    "measurement cache), and the whole batch is evaluated "
                    "in ONE jit-compiled call — no kernel is ever timed.")
    ap.add_argument("profile", help="machine-profile JSON path")
    ap.add_argument("--tags", nargs="+", default=None,
                    help="UIPiCK filter tags selecting kernels to predict")
    ap.add_argument("--kernel", action="append", default=[],
                    metavar="NAME",
                    help="built-in Pallas kernel target to predict "
                         "(repeatable; e.g. kernels.ops.matmul — see "
                         "repro.analysis.targets), costed statically "
                         "from grid/block specs, never executed")
    ap.add_argument("--match", choices=sorted(_MATCH), default="intersect",
                    help="generator tag match condition")
    ap.add_argument("--model", default=None,
                    help="fit name inside the profile (default: "
                         "ovl_flop_mem, or the profile's only fit)")
    ap.add_argument("--cache-dir", default=None,
                    help="measurement cache; cached counts skip jaxpr "
                         "tracing")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write predictions (with breakdowns) as JSON")
    ap.add_argument("--explain", type=int, default=0, metavar="N",
                    help="print the top-N breakdown terms per kernel")
    ap.add_argument("--strict-scope", action="store_true",
                    help="error on kernels whose counted work the model "
                         "has no term for")
    ap.add_argument("--audit", action="store_true",
                    help="print the static modelability audit of the "
                         "selected kernels against the fit (scope gaps, "
                         "signature hazards, holdout identifiability) "
                         "before predicting — observability only, never "
                         "changes the exit code")
    ap.add_argument("--expect-zero-timings", action="store_true",
                    help="exit 1 if any kernel timing pass ran (they "
                         "never should during prediction)")
    args = ap.parse_args(argv)

    from repro.api import PerfSession, PredictionError
    try:
        session = PerfSession.open(args.profile, cache=args.cache_dir)
    except ProfileError as e:
        print(f"[predict] {e}", file=sys.stderr)
        return 3
    items: List = []
    names: List[str] = []
    if args.tags:
        kernels = KernelCollection(ALL_GENERATORS).generate_kernels(
            args.tags, generator_match_cond=_MATCH[args.match])
        if not kernels:
            print(f"[predict] no measurement kernels match tags "
                  f"{args.tags!r}", file=sys.stderr)
            return 2
        items.extend(kernels)
        names.extend(k.name for k in kernels)
    if args.kernel:
        from repro.analysis.targets import kernel_targets
        targets = {t.name: t for t in kernel_targets()}
        for name in args.kernel:
            t = targets.get(name)
            if t is None:
                print(f"[predict] unknown --kernel {name!r}; built-in "
                      f"targets: {', '.join(sorted(targets))}",
                      file=sys.stderr)
                return 2
            items.append((t.fn, t.args))
            names.append(t.name)
    if not items:
        print("[predict] nothing to predict: pass --tags and/or --kernel",
              file=sys.stderr)
        return 2
    if args.audit:
        report = session.audit(items, model=args.model)
        for line in report.render().splitlines():
            print(f"[audit] {line}")
    try:
        preds = session.predict_batch(items, model=args.model,
                                      names=names,
                                      strict=args.strict_scope)
    except PredictionError as e:
        print(f"[predict] {e}", file=sys.stderr)
        return 3
    for p in preds:
        if args.explain:
            print(p.explain(top=args.explain))
        else:
            print(f"[predict] {p.kernel}: {p.seconds:.6g} s")
    if args.json_out:
        payload = {
            "fingerprint": session.profile.fingerprint.id,
            "model": preds[0].model,
            "predictions": [p.to_dict() for p in preds],
        }
        Path(args.json_out).write_text(
            json.dumps(payload, indent=2, sort_keys=True))
        print(f"[predict] json -> {args.json_out}")
    diag = preds[0].diagnostics
    gmre = diag.get("holdout_gmre")
    print(f"[predict] kernels={len(preds)} model={preds[0].model} "
          f"held-out gmre="
          f"{'n/a' if gmre is None else f'{gmre * 100:.2f}%'}")
    print(f"[predict] timings_performed={session.timer.calls} "
          f"batched_evals={session.eval_calls} "
          f"traces={session.trace_count} "
          f"count_traces={session.engine.trace_count} "
          f"count_hits={session.engine.hits}")
    if args.expect_zero_timings and session.timer.calls:
        print(f"[predict] FAIL: prediction must never time kernels but "
              f"{session.timer.calls} timing passes ran", file=sys.stderr)
        return 1
    return 0


def _cmd_compare(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate compare",
        description="Cross-machine accuracy report from ≥2 study profiles "
                    "(per-model × per-kernel-variant held-out relative "
                    "error).")
    ap.add_argument("profiles", nargs="+",
                    help="machine-profile or fleet-bundle JSON paths")
    ap.add_argument("--report", default=None,
                    help="markdown report destination (default: stdout)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="JSON report destination")
    ap.add_argument("--sweep", action="store_true",
                    help="append the scope-vs-accuracy curve (held-out "
                         "gmre per zoo rank) to the report and JSON")
    args = ap.parse_args(argv)

    from repro.studies import (
        StudyError,
        compare_profiles,
        load_profiles_any,
        scope_accuracy_sweep,
        sweep_to_markdown,
    )
    try:
        profiles = [p for path in args.profiles
                    for p in load_profiles_any(path)]
        report = compare_profiles(profiles)
    except (StudyError, ProfileError, ValueError) as e:
        # ValueError: malformed holdout data (zero outputs, missing
        # feature columns) surfaced by the accuracy evaluation
        print(f"[compare] {e}", file=sys.stderr)
        return 3
    md = report.to_markdown()
    sweep = None
    if args.sweep:
        sweep = scope_accuracy_sweep(report)
        md = md + "\n" + sweep_to_markdown(sweep)
    if args.report:
        Path(args.report).write_text(md)
        print(f"[compare] report -> {args.report}")
    else:
        print(md)
    if args.json_out:
        payload = report.to_json_dict()
        if sweep is not None:
            payload["sweep"] = sweep["sweep"]
        Path(args.json_out).write_text(
            json.dumps(payload, indent=2, sort_keys=True))
        print(f"[compare] json -> {args.json_out}")
    for fp in report.machines:
        summary = " ".join(f"{m}={report.summary[fp][m] * 100:.2f}%"
                           for m in report.model_names
                           if m in report.summary[fp])
        print(f"[compare] {fp}: {summary}")
    if sweep is not None:
        for row in sweep["sweep"]:
            rank = row["scope_rank"]
            fleet = row["fleet_gmre"]
            print(f"[compare] sweep rank="
                  f"{'-' if rank is None else rank} {row['model']} "
                  f"params={row['n_params']} fleet gmre="
                  f"{'n/a' if fleet is None else f'{fleet * 100:.2f}%'}")
    return 0


def _cmd_merge(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate merge",
        description="Merge profiles.  Same machine: union of fits "
                    "(conflicts are errors).  Different machines: "
                    "requires --fleet, producing a fleet bundle.")
    ap.add_argument("profiles", nargs="+",
                    help="machine-profile or fleet-bundle JSON paths")
    ap.add_argument("--out", required=True, help="output JSON path")
    ap.add_argument("--fleet", action="store_true",
                    help="allow cross-machine inputs; write a fleet bundle")
    args = ap.parse_args(argv)

    from repro.checkpoint.manager import atomic_write_json
    from repro.studies import (
        StudyError, fleet_to_dict, load_profiles_any, merge_any,
    )
    try:
        profiles = [p for path in args.profiles
                    for p in load_profiles_any(path)]
        if len(profiles) < 2:
            print(f"[merge] need ≥ 2 profiles, got {len(profiles)}",
                  file=sys.stderr)
            return 3
        merged = merge_any(profiles, allow_cross_machine=args.fleet)
    except (StudyError, ProfileError, ValueError) as e:
        print(f"[merge] {e}", file=sys.stderr)
        return 3
    if args.fleet:
        atomic_write_json(Path(args.out), fleet_to_dict(merged))
        print(f"[merge] fleet bundle ({len(merged)} machines) -> "
              f"{args.out}")
    else:
        save_profile(merged[0], args.out)
        print(f"[merge] profile ({len(merged[0].fits)} fits) -> {args.out}")
    return 0


def _cmd_gc(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate gc",
        description="Evict measurement-cache entries: corrupt files, "
                    "entries from other devices, entries older than "
                    "--max-age.")
    ap.add_argument("--cache-dir", required=True,
                    help="measurement cache directory to sweep")
    ap.add_argument("--max-age", type=float, default=None, metavar="SECONDS",
                    help="also drop entries older than this many seconds")
    ap.add_argument("--keep-foreign", action="store_true",
                    help="keep entries from other device fingerprints")
    ap.add_argument("--counts", action="store_true",
                    help="also sweep the count-engine store (cached "
                         "concrete counts + symbolic family "
                         "reconstructions) beside the measurement cache")
    args = ap.parse_args(argv)

    cache = MeasurementCache(args.cache_dir, DeviceFingerprint.local())
    stats = cache.gc(max_age=args.max_age,
                     drop_foreign=not args.keep_foreign)
    print(f"[gc] kept={stats.kept} dropped_foreign={stats.dropped_foreign} "
          f"dropped_old={stats.dropped_old} "
          f"dropped_corrupt={stats.dropped_corrupt} "
          f"dropped_schema={stats.dropped_schema}")
    if args.counts:
        from repro.core.countengine import CountEngine
        cstats = CountEngine(store=cache.count_store).gc(
            max_age=args.max_age)
        print(f"[gc] counts: kept={cstats.kept} "
              f"dropped_old={cstats.dropped_old} "
              f"dropped_corrupt={cstats.dropped_corrupt} "
              f"dropped_schema={cstats.dropped_schema}")
    return 0


_SUBCOMMANDS = {"predict": _cmd_predict, "compare": _cmd_compare,
                "merge": _cmd_merge, "gc": _cmd_gc}


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in _SUBCOMMANDS:
        return _SUBCOMMANDS[argv[0]](argv[1:])
    return _calibrate(argv)


if __name__ == "__main__":
    sys.exit(main())
