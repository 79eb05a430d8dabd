"""A closed loop of calibrations, each from an empty measurement cache.

Set-up builds the configuration's subjects from the seed on the device,
makes one calibration to warm every program the loop runs, prices the
subjects once, and times each subject on the host clock (each call
ending in ``block_until_ready``, the median over groups of calls that
each span a few tenths of a second).  The window then
calibrates back to back through ``repro.calibrate --zoo`` and prices the
subjects from each new profile with ``PerfSession.predict_batch``.

End-to-end: ``profile_s``, from the window's start to the end of the last
profile begun in it, over the profiles completed.  Per layer (``ctx``):
``price_err_pct``, the geometric-mean relative error of every window
profile's subject prices against the subjects' measured times, which
swings too far from run to run to hold to a bound (PERF.md).

``correct``: for every window profile, the battery's counts and the
subjects' counts against the plain counter, each linear rung's fit
against a float64 fit of the same measured rows (by the objective), and
each subject's price against the rung evaluated in float64 from the
fitted parameters and the plain counts.
"""
from __future__ import annotations

import math
import shutil
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from bench import reference as R
from bench import subjects as S


def _timed(fn, args, warmup: int, group_s: float, groups: int) -> float:
    """Host-clock seconds of one subject call, each call ending in
    ``block_until_ready`` as the battery times its kernels: the median
    over ``groups`` groups of back-to-back calls, each group long enough
    (``group_s``) that the clock's own error is small beside it."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    n = max(1, math.ceil(group_s / (time.perf_counter() - t0)))
    ts = []
    for _ in range(groups):
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) / n)
    return statistics.median(ts)


def _gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0))) \
        if a.size else 0.0


def run(h) -> Dict[str, Any]:
    from repro.api import PerfSession
    from repro.core import uipick
    from repro.profiles import cli
    from repro.studies import study

    tr, specs = h.traffic, h.config["subjects"]
    names = [s["name"] for s in specs]
    argv = ["--zoo", "--tags", *tr["tags"], "--trials", str(tr["trials"])]

    # the benchmark's spans around the calls into each layer; the gather
    # also keeps what it measured, for the reference
    gathers: List[tuple] = []
    orig_gather = study.gather_feature_table

    def gather(features, kernels, **kw):
        with h.spans.span("bench.measure"):
            table = orig_gather(features, kernels, **kw)
        gathers.append((list(kernels), table))
        return table

    orig_time = uipick.MeasurementKernel.time_stats

    def time_stats(self, *a, **kw):
        with h.spans.span("bench.kernel:" + self.name, kernel=self.name,
                          tags=dict(self.tags),
                          module="jit_" + getattr(self.fn, "__name__", "")):
            return orig_time(self, *a, **kw)

    study.gather_feature_table = gather
    uipick.MeasurementKernel.time_stats = time_stats
    undo_fit = h.spans.wrap(study, "fit_models", "bench.solve")
    try:
        return _run(h, cli, PerfSession, specs, names, argv, gathers, tr)
    finally:
        study.gather_feature_table = orig_gather
        uipick.MeasurementKernel.time_stats = orig_time
        undo_fit()


def _run(h, cli, PerfSession, specs, names, argv, gathers, tr):
    items = [S.build(s) for s in specs]

    def profile(i: int):
        d = h.scratch / f"profile{i}"
        shutil.rmtree(d, ignore_errors=True)
        with h.spans.span("bench.profile"):
            rc = cli.main(argv + ["--cache-dir", str(d / "measurements"),
                                  "--out", str(d / "profile.json")])
            if rc != 0:
                raise RuntimeError(f"repro.calibrate exited {rc}")
            with h.spans.span("bench.price"):
                session = PerfSession.open(str(d / "profile.json"))
                preds = session.predict_batch(items, names=names)
        fits = {k: dict(v.params) for k, v in session.profile.fits.items()}
        shutil.rmtree(d, ignore_errors=True)
        return {"fits": fits, "gather": len(gathers) - 1, "preds": preds}

    # ---- set-up: one calibration warms every program the loop runs
    profile(0)
    timing = tr["timing"]
    measured = []
    for i, spec in enumerate(specs):
        fn, args = S.build(spec, key=h.key(i))
        measured.append(_timed(fn, args, int(timing["warmup"]),
                               float(timing["group_s"]),
                               int(timing["groups"])))
        del fn, args
    print(f"[bench] subjects measured: "
          f"{dict(zip(names, measured))}", flush=True)

    done: List[Dict[str, Any]] = []
    attempted = failed = 0
    with h.window():
        t0 = time.perf_counter()
        end = t0 + h.seconds
        last = t0
        while time.perf_counter() < end:
            attempted += 1
            try:
                done.append(profile(attempted))
            except Exception as e:     # noqa: BLE001 — counted as failed
                failed += 1
                print(f"[bench] profile {attempted} failed: {e!r}",
                      flush=True)
            last = time.perf_counter()
    h.finish()

    errs = [abs(p.seconds - m) / m for r in done
            for p, m in zip(r["preds"], measured)]
    metrics, err = {}, None
    if done:
        metrics["profile_s"] = (last - t0) / len(done)
        err = 100.0 * math.exp(
            sum(math.log(max(e, 1e-12)) for e in errs) / len(errs))
    print(f"[bench] price_err_pct {err}", flush=True)
    ctx = h.ctx(preds=done[-1]["preds"] if done else [],
                profiles=len(done), price_err_pct=err)
    t_ref = time.perf_counter()
    checks = _checks(h, done, gathers, items, tr["limits"], failed)
    print(f"[bench] reference took {time.perf_counter() - t_ref:.1f} s",
          flush=True)
    return {"attempted": attempted, "failed": failed, "metrics": metrics,
            "ctx": ctx, "checks": checks}


def _checks(h, done, gathers, items, limits, failed):
    """The window's profiles against the plain reference."""
    import jax
    import jax.numpy as jnp

    sub_counts = [R.count(fn, args) for fn, args in items]
    battery: Dict[str, Dict[str, float]] = {}
    count_gap = fit_gap = price_gap = 0.0
    for r in done:
        kernels, table = gathers[r["gather"]]
        for k in kernels:
            if k.name not in battery:
                battery[k.name] = R.count(k.fn, jax.eval_shape(k.make_args))
        ref = {f: np.array([battery[k.name][f] for k in kernels])
               for f in R.FEATURES}
        count_gap = max(count_gap, *(_gap(table.column(f), ref[f])
                                     for f in R.FEATURES))
        seconds = table.column(next(f for f in table.feature_ids
                                    if f.startswith("f_wall_time")))
        train, _ = R.holdout(table.row_names)
        rc = {f: v[train] for f, v in ref.items()}
        fits = r["fits"]
        for rung in R.LINEAR:
            ours = R.fit(rung, rc, seconds[train])
            theirs = fits[rung]
            if h.control:
                # the reference's own fit, held in bfloat16
                theirs = {k: float(jnp.asarray(v, jnp.bfloat16))
                          for k, v in ours.items()}
            c_ref = R.cost(rung, ours, rc, seconds[train])
            c_prog = R.cost(rung, theirs, rc, seconds[train])
            fit_gap = max(fit_gap, (c_prog - c_ref) / c_ref)
        for p, c in zip(r["preds"], sub_counts):
            count_gap = max(count_gap, _gap([p.features.get(f, 0.0)
                                             for f in R.FEATURES],
                                            [c[f] for f in R.FEATURES]))
            params = fits[p.model]
            want = float(R.price(p.model, params, c))
            got = p.seconds
            if h.control:
                got = float(R.price(p.model, params, c, xp=jnp,
                                    dtype=jnp.bfloat16))
            price_gap = max(price_gap, abs(got - want) / want)
    return {
        "unfinished_profiles": {"value": failed, "limit": 0},
        "count_gap": {"value": count_gap, "limit": limits["count_gap"]},
        "fit_gap": {"value": fit_gap, "limit": limits["fit_gap"]},
        "price_gap": {"value": price_gap, "limit": limits["price_gap"]},
    }
