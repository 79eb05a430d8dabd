"""``PerfSession`` — one object from kernel → counts → prediction.

The paper's workflow, previously hand-wired across four packages
(``count_fn`` → ``FeatureCounts`` → feature alignment → ``MachineProfile``
→ ``Model.batched_eval``), behind a single facade::

    from repro import PerfSession

    session = PerfSession.open("machine_profile.json")
    pred = session.predict(lambda a, b: a @ b, x, y, model="ovl_flop_mem")
    print(pred.seconds, pred.breakdown)        # cost-explanatory
    preds = session.predict_batch(kernels)     # one jit-compiled eval

Opening from a profile path performs ZERO measurements; opening from a
device (``None`` = this machine, or a synthetic ground-truth device) runs
the cache-backed calibration study on demand.  Prediction never times a
kernel: features come from the one-pass jaxpr counter (or the measurement
cache), and every batch is evaluated in a single jit-compiled
``batched_breakdown`` call, so throughput scales with batch size, not
Python dispatch.  ``eval_calls``/``trace_count`` make that claim
observable — tests assert exactly one compiled evaluation per batch.

**Layering (and thread safety).**  ``PerfSession`` is the *resource*
layer: it owns profile lifecycle (open / calibrate / save), the
measurement cache, the amortized count engine, and the injectable timer
seam.  The prediction *math* lives in the pure
:class:`repro.api.engine.PredictEngine` it wraps
(``session.predict_engine``).  Concurrent ``predict``/``predict_batch``
calls on one session are safe — the predict engine and the count engine
each serialize their internal state — which is what
:mod:`repro.serving` relies on; ``open``/calibration, which mutate
resources, are not meant to race.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from repro import spans
from repro.api.engine import DEFAULT_MODEL, PredictEngine
from repro.api.errors import PredictionError
from repro.api.prediction import Prediction
from repro.core.countengine import (
    CountEngine,
    args_signature,
    callable_signature,
)
from repro.core.counting import FeatureCounts
from repro.core.model import Model
from repro.core.uipick import CountingTimer, MeasurementKernel
from repro.profiles.cache import MeasurementCache
from repro.profiles.fingerprint import DeviceFingerprint
from repro.profiles.profile import (
    MachineProfile,
    ModelFit,
    ProfileError,
    load_profile,
    save_profile,
)

__all__ = ["DEFAULT_MODEL", "PerfSession", "PredictItem"]

# one predict_batch item: a measurement kernel, a bare callable, or a
# (callable, example_args) pair
PredictItem = Union[MeasurementKernel, Callable, Tuple[Callable, tuple]]


class PerfSession:
    """A loaded-and-validated machine profile plus every *resource* needed
    to predict with it: the pure :class:`PredictEngine` (compiled
    per-model evaluators), the measurement cache, the count engine, and
    the injectable timer seam (used only if calibration runs)."""

    def __init__(self, profile: MachineProfile, *,
                 cache: Optional[MeasurementCache] = None,
                 timer: Optional[CountingTimer] = None,
                 engine: Optional[CountEngine] = None,
                 calibration: Optional[Dict[str, Any]] = None):
        self.profile = profile
        self.cache = cache
        self.timer = _as_counting_timer(timer)
        # the amortized counting engine: in-process memo + a persistent
        # tier beside the measurement cache (when one is attached), so a
        # warm serving process performs zero jaxpr traces —
        # engine.trace_count is the probe that claim is asserted against
        self.engine = engine if engine is not None else CountEngine(
            store=cache.count_store if cache is not None else None)
        # how this session's profile came to be (observability: the CLI
        # prints it, tests assert the zero-timing warm path against it)
        self.calibration: Dict[str, Any] = dict(calibration or {})
        # the pure prediction core (model resolution + compiled batched
        # evaluators); shared safely across request threads by a daemon
        self.predict_engine = PredictEngine(profile)

    # the batched-evaluation probes live on the predict engine now; these
    # stay readable here so `session.eval_calls == 1`-style assertions
    # (and the CLI's summary line) keep working unchanged
    @property
    def eval_calls(self) -> int:
        """Compiled ``batched_breakdown`` dispatches performed."""
        return self.predict_engine.eval_calls

    @property
    def trace_count(self) -> int:
        """Jit (re)traces of the batched evaluator."""
        return self.predict_engine.trace_count

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def open(cls, source: Union[None, str, Path, MachineProfile,
                                Any] = None, *,
             tags: Optional[Sequence[str]] = None,
             trials: int = 8,
             cache: Union[None, str, Path, MeasurementCache] = None,
             expected_fingerprint: Union[None, str,
                                         DeviceFingerprint] = None,
             holdout_fraction: float = 0.25,
             retime_rel_std: Optional[float] = None,
             timer: Optional[Callable] = None,
             engine: Optional[CountEngine] = None,
             save_to: Union[None, str, Path] = None) -> "PerfSession":
        """Open a prediction session.

        ``source`` selects where the fitted models come from:

        * a **path** — load + strictly validate an existing profile
          (``ProfileError`` on corruption, wrong schema, or — when
          ``expected_fingerprint`` is a fingerprint or the string
          ``"local"`` — foreign hardware).  Zero measurements.
        * a **MachineProfile** — wrap it directly.
        * ``None`` — calibrate THIS machine on demand: the full
          cache-backed study (gather → zoo multi-fit → holdout) with
          ``tags``/``trials``/``retime_rel_std`` forwarded.
        * a **device object** exposing ``.fingerprint`` and ``.timer``
          (e.g. :class:`repro.testing.synthdev.SyntheticDevice`) —
          calibrate that device through its injectable timer.

        ``cache`` may be a :class:`~repro.profiles.MeasurementCache` or a
        directory path; it serves calibration timings AND count lookups
        during prediction.  ``save_to`` persists an on-demand calibration
        as a normal profile artifact.
        """
        with spans.span("price.open"):
            if isinstance(source, MachineProfile):
                profile = source
                _check_fingerprint(profile, expected_fingerprint)
                return cls(profile,
                           cache=_as_cache(cache, profile.fingerprint),
                           timer=timer, engine=engine,
                           calibration={"source": "profile", "timings": 0,
                                        "retimed": 0})
            if isinstance(source, (str, Path)):
                fp = expected_fingerprint
                if fp == "local":
                    fp = DeviceFingerprint.local()
                profile = load_profile(source, expected_fingerprint=fp)
                return cls(profile,
                           cache=_as_cache(cache, profile.fingerprint),
                           timer=timer, engine=engine,
                           calibration={"source": f"profile:{source}",
                                        "timings": 0, "retimed": 0})

            # calibrate on demand (local hardware or an injectable device)
            from repro.studies.study import run_study
            from repro.studies.zoo import STUDY_TAGS

            if source is None:
                fingerprint = DeviceFingerprint.local()
                base_timer = timer
            elif hasattr(source, "fingerprint") and hasattr(source, "timer"):
                fingerprint = source.fingerprint
                base_timer = timer or source.timer
            else:
                raise TypeError(
                    f"PerfSession.open expects a profile path, a "
                    f"MachineProfile, a device with .fingerprint/.timer, or "
                    f"None (this machine); got {type(source).__name__}")
            counting = _as_counting_timer(base_timer)
            mcache = _as_cache(cache, fingerprint)
            if engine is None:
                engine = CountEngine(
                    store=mcache.count_store if mcache is not None else None)
            profile = run_study(
                fingerprint=fingerprint, timer=counting, cache=mcache,
                tags=tags or STUDY_TAGS, trials=trials,
                holdout_fraction=holdout_fraction,
                retime_rel_std=retime_rel_std, engine=engine)
            if save_to is not None:
                save_profile(profile, save_to)
            return cls(profile, cache=mcache, timer=counting, engine=engine,
                       calibration={
                           "source": f"calibrated:{fingerprint.id}",
                           "timings": counting.calls,
                           "cache_hits": mcache.hits if mcache else 0,
                           "count_traces": engine.trace_count,
                           "retimed": len(getattr(profile, "retimed_rows",
                                                  [])),
                       })

    # ------------------------------------------------------------------
    # prediction
    # ------------------------------------------------------------------

    def predict(self, fn: PredictItem, *args,
                model: Optional[str] = None,
                name: Optional[str] = None,
                strict: bool = False) -> Prediction:
        """Predict one kernel: ``fn`` is a jit-able callable (called with
        ``*args`` example arguments for counting) or a
        :class:`MeasurementKernel`.  Counts the jaxpr once, aligns against
        the fitted model, evaluates through the same compiled batched path
        as :meth:`predict_batch` (batch of one)."""
        item: PredictItem = fn if isinstance(fn, MeasurementKernel) \
            else (fn, args)
        return self.predict_batch(
            [item], model=model,
            names=[name] if name is not None else None,
            strict=strict)[0]

    def predict_batch(self, items: Sequence[PredictItem], *,
                      model: Optional[str] = None,
                      names: Optional[Sequence[str]] = None,
                      strict: bool = False) -> List[Prediction]:
        """Predict every item in ONE jit-compiled batched model
        evaluation: rows are packed into a single dense feature matrix and
        the per-term breakdown of the whole batch comes back from one
        compiled call — zero kernel timings, no per-row Python dispatch.

        ``strict=True`` turns out-of-scope work into a typed
        :class:`PredictionError` collecting EVERY violating kernel of the
        batch (``error.violations`` maps each back to its index, naming
        the unmodeled features and the UIPiCK tags that would calibrate
        them); the default records such features per prediction in
        ``Prediction.unmodeled``.

        Duplicate items — identical (content signature, argument shapes)
        — are counted ONCE and their feature rows broadcast, so a batch
        of 64 requests over 8 distinct kernels costs 8 count lookups (and
        zero traces when the count cache is warm).
        """
        items = list(items)
        if not items:
            return []
        with spans.span("price.batch", rows=len(items)):
            self.predict_engine.resolve(model)  # fail fast, pre-counting
            kernel_names, counts_rows = self._count_items(items, names)
            return self.predict_engine.predict_rows(
                counts_rows, kernel_names, model=model, strict=strict)

    def try_predict_batch(self, items: Sequence[PredictItem], *,
                          model: Optional[str] = None,
                          names: Optional[Sequence[str]] = None,
                          strict: bool = True
                          ) -> List[Union[Prediction, PredictionError]]:
        """Per-item error mode of :meth:`predict_batch` — the coalescing
        daemon's entry point: position *i* of the result is either item
        *i*'s :class:`Prediction` or its own :class:`PredictionError`, so
        one out-of-scope request never fails the whole coalesced batch
        (which still costs a single compiled evaluation)."""
        items = list(items)
        if not items:
            return []
        with spans.span("price.batch", rows=len(items)):
            self.predict_engine.resolve(model)
            kernel_names, counts_rows = self._count_items(items, names)
            return self.predict_engine.try_predict_rows(
                counts_rows, kernel_names, model=model, strict=strict)

    def _count_items(self, items: Sequence[PredictItem],
                     names: Optional[Sequence[str]]
                     ) -> Tuple[List[str], List[FeatureCounts]]:
        """The resource half of a batched predict: resolve item identity,
        dedup by (signature, shapes), and gather counts through the cache
        and count engine — never through a timer."""
        if names is not None and len(names) != len(items):
            raise ValueError(f"names has {len(names)} entries for "
                             f"{len(items)} items")
        kernel_names: List[str] = []
        counts_rows: List[FeatureCounts] = []
        deduped: Dict[Any, FeatureCounts] = {}
        for idx, item in enumerate(items):
            kname, key, sig = self._item_identity(item, idx)
            kernel_names.append(names[idx] if names is not None else kname)
            counts = deduped.get(key) if key is not None else None
            if counts is None:
                counts = self._counts_of(item, idx, sig)
                if key is not None:
                    deduped[key] = counts
            counts_rows.append(counts)
        return kernel_names, counts_rows

    # ------------------------------------------------------------------
    # static modelability audit
    # ------------------------------------------------------------------

    def audit(self, items: Optional[Sequence[PredictItem]] = None, *,
              model: Optional[str] = None):
        """Static modelability audit of this session — no kernel runs, no
        timings, only abstract traces (the report's ``stats`` prove it).

        Audits the resolved fit's identifiability against the profile's
        held-out battery (when the profile carries one), plus — for each
        given predict item — the jaxpr scope, cache-signature hazards,
        and any counted work outside the model's scope
        (``out-of-scope-feature``, the static twin of ``strict=True``
        prediction).  Returns a
        :class:`repro.analysis.DiagnosticReport`."""
        from repro.analysis import DiagnosticReport, Diagnostic
        from repro.analysis.identifiability import analyze_model
        from repro.analysis.scope import abstract_args, audit_callable
        from repro.analysis.sighazards import audit_signature
        from repro.core.counting import count_fn

        fit_name, _mf, m = self._resolve_model(model)
        report = DiagnosticReport(stats={"timings": 0, "traces": 0})
        holdout = self.profile.holdout
        if holdout is not None and len(holdout):
            report.extend(analyze_model(
                m, m.align(holdout, missing="zero"),
                f"model:{fit_name}[holdout]"))
        for idx, item in enumerate(items or ()):
            kname, _key, _sig = self._item_identity(item, idx)
            loc = f"kernel:{kname}"
            if isinstance(item, MeasurementKernel):
                fn, args = item.fn, abstract_args(item.make_args)
            elif isinstance(item, tuple):
                fn, args = item
            else:
                fn, args = item, ()
            report.extend(audit_callable(fn, args, loc,
                                         stats=report.stats))
            report.extend(audit_signature(fn, loc))
            try:
                counts = count_fn(fn, *args)
                report.stats["traces"] += 1
            except Exception:   # noqa: BLE001 — already diagnosed above
                continue
            extra = m.unmodeled_features(counts)
            if extra:
                report.extend([Diagnostic(
                    "warning", "out-of-scope-feature", loc,
                    f"kernel performs counted work model {fit_name!r} "
                    f"has no term for: {', '.join(sorted(extra))} — "
                    f"predictions silently omit that cost "
                    f"(strict=True prediction would refuse)",
                    details={"features": sorted(extra),
                             "model": fit_name})])
        return report

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _resolve_model(self, model: Optional[str]
                       ) -> Tuple[str, ModelFit, Model]:
        return self.predict_engine.resolve(model)

    def _item_identity(self, item: PredictItem, idx: int
                       ) -> Tuple[str, Optional[Any], str]:
        """Display name + dedup key + content signature of one predict
        item.  The key is the item's content identity — (signature,
        shapes) — so identical requests in a batch collapse to one count
        lookup; a ``""`` signature means no sound identity exists (the key
        falls back to object identity, sound in-batch only, and the
        engine traces per shape).  The signature rides back so the
        engine never recomputes the state walk for the same item."""
        if isinstance(item, MeasurementKernel):
            sig = item.code_sig or callable_signature(item.fn)
            # with sig "": same fn OBJECT + name/sizes is sound in-batch
            key_sig = sig or f"obj:{id(item.fn)}"
            return item.name, ("kern", key_sig, item.name,
                               tuple(sorted(item.sizes.items()))), sig
        if isinstance(item, tuple):
            fn, args = item
        elif callable(item):
            fn, args = item, ()
        else:
            raise TypeError(
                f"predict item #{idx} must be a MeasurementKernel, a "
                f"callable, or a (callable, args) pair; "
                f"got {type(item).__name__}")
        kname = getattr(fn, "__name__", "kernel")
        if kname == "<lambda>":
            kname = "kernel"
        sig = callable_signature(fn)
        key = ("fn", sig or f"obj:{id(fn)}", args_signature(args))
        return f"{kname}[{idx}]", key, sig

    def _counts_of(self, item: PredictItem, idx: int, sig: str
                   ) -> FeatureCounts:
        """One kernel's counted features — through the measurement cache
        and the count engine when the item has a stable identity, never
        through a timer."""
        if isinstance(item, MeasurementKernel):
            trials = self.profile.trials
            if self.cache is not None:
                entry = self.cache.get(item, trials)
                if entry is not None:
                    return entry.counts
                counts = self.engine.counts_for(item, sig=sig)
                # counts-only entry: a later gather backfills the timing
                self.cache.put(item, trials, None, counts)
                return counts
            return self.engine.counts_for(item, sig=sig)
        if isinstance(item, tuple):
            fn, args = item
            return self.engine.counts_of_callable(fn, args, sig=sig)
        return self.engine.counts_of_callable(item, sig=sig)

def _as_counting_timer(timer) -> CountingTimer:
    if isinstance(timer, CountingTimer):
        return timer
    return CountingTimer(timer) if timer is not None else CountingTimer()


def _as_cache(cache, fingerprint) -> Optional[MeasurementCache]:
    if cache is None or isinstance(cache, MeasurementCache):
        return cache
    return MeasurementCache(cache, fingerprint)


def _check_fingerprint(profile: MachineProfile, expected) -> None:
    if expected is None:
        return
    if expected == "local":
        expected = DeviceFingerprint.local()
    if profile.fingerprint != expected:
        raise ProfileError(
            f"profile was calibrated on {profile.fingerprint.id!r} but "
            f"{expected.id!r} was required; recalibrate with "
            f"`python -m repro.calibrate`")
