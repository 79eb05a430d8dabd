"""``PredictEngine`` — the pure prediction core: (profile, counts) →
:class:`Prediction`.

The facade used to be one object; serving splits it in two (the ROADMAP
item 1 refactor):

* **this engine** holds the profile and the prediction *math only* —
  model resolution, feature alignment, the jit-compiled
  ``batched_breakdown`` evaluator, per-term assembly.  Its inputs are
  explicit (:class:`~repro.core.counting.FeatureCounts` rows the caller
  already gathered); it owns no measurement cache, no count engine, no
  timer, and never touches the filesystem.
* the **resource layer** (:class:`repro.api.session.PerfSession`) owns
  everything stateful around it: profile lifecycle (open / calibrate /
  save), the measurement cache, the amortized count engine, and the
  injectable timer seam.

**Thread safety.**  The engine is safe to share across request threads:
its memo tables (compiled evaluators, resolved fits, fit diagnostics)
and observability counters are guarded by one internal lock, and
evaluation itself is functional.  The resource layer is thread-safe for
*prediction* (its count engine serializes internally) but not for
concurrent open/calibrate — see the session docstring.

``eval_calls``/``trace_count`` keep their PR-4 semantics: one batched
dispatch per ``predict_rows`` call, one jit trace per distinct model
signature — a serving daemon's coalescing win is asserted against
exactly these probes.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, \
    Union

import jax
import jax.numpy as jnp

from repro import spans
from repro.api.errors import (
    PredictionError,
    scope_violation,
    scope_violation_error,
)
from repro.api.prediction import Prediction, assemble_predictions
from repro.core.calibrate import gmre_of, relative_errors
from repro.core.counting import FeatureCounts
from repro.core.model import Model, _param_dtype
from repro.profiles.profile import MachineProfile, ModelFit, ProfileError

#: default fit to predict with when the caller names none and the profile
#: carries several (the zoo's widest-scope form)
DEFAULT_MODEL = "ovl_flop_mem"


class PredictEngine:
    """Stateless-by-contract prediction math over ONE machine profile.

    "Stateless" here means *no resources*: every attribute is either the
    immutable profile, a pure memo keyed by profile content (compiled
    evaluators, resolved fits), or an observability counter.  Given the
    same (counts, model) inputs it always returns the same predictions —
    which is what makes it safe to park behind a daemon and share across
    every request thread.
    """

    def __init__(self, profile: MachineProfile):
        self.profile = profile
        # batched-evaluation observability: dispatches and (re)traces of
        # the jit-compiled breakdown evaluator
        self.eval_calls = 0
        self.trace_count = 0
        self._lock = threading.Lock()
        self._compiled: Dict[str, Callable] = {}
        self._fit_diag: Dict[str, Dict[str, Any]] = {}
        # resolved (ModelFit, Model) per fit name: ModelFit.model() builds
        # a fresh Model (AST parse + breakdown-plan compile) — pay that
        # once per fit, not once per predict on the serving hot path
        self._resolved: Dict[str, Tuple[ModelFit, Model]] = {}

    # ------------------------------------------------------------------
    # model resolution
    # ------------------------------------------------------------------

    def resolve(self, model: Optional[str]
                ) -> Tuple[str, ModelFit, Model]:
        """Resolve a fit name (or the default) to its validated
        (name, ModelFit, compiled Model) triple, memoized."""
        fits = self.profile.fits
        name = model
        if name is None:
            if DEFAULT_MODEL in fits:
                name = DEFAULT_MODEL
            elif len(fits) == 1:
                name = next(iter(fits))
            else:
                raise PredictionError(
                    f"profile for {self.profile.fingerprint.id!r} carries "
                    f"fits {self.profile.fit_names} and none is the "
                    f"default {DEFAULT_MODEL!r}; pass model=<name>")
        with self._lock:
            cached = self._resolved.get(name)
        if cached is not None:
            return name, *cached
        try:
            mf = self.profile.get_fit(name)
        except ProfileError as e:
            raise PredictionError(str(e)) from e
        m = mf.model()
        missing = [p for p in m.param_names if p not in mf.params]
        if missing:
            raise PredictionError(
                f"fit {name!r} lacks fitted values for parameter(s) "
                f"{missing} of its own expression — the profile was "
                f"edited or corrupted; recalibrate")
        with self._lock:
            self._resolved[name] = (mf, m)
        return name, mf, m

    # ------------------------------------------------------------------
    # prediction
    # ------------------------------------------------------------------

    def predict_rows(self, counts_rows: Sequence[FeatureCounts],
                     kernel_names: Sequence[str], *,
                     model: Optional[str] = None,
                     strict: bool = False) -> List[Prediction]:
        """Predict one row per counted kernel in ONE jit-compiled batched
        evaluation.  ``strict=True`` raises a single
        :class:`PredictionError` collecting EVERY out-of-scope row (its
        ``violations`` list maps each back to its batch index)."""
        preds, errors = self._predict(counts_rows, kernel_names,
                                      model=model, strict=strict,
                                      partial=False)
        assert not errors
        return preds

    def try_predict_rows(self, counts_rows: Sequence[FeatureCounts],
                         kernel_names: Sequence[str], *,
                         model: Optional[str] = None,
                         strict: bool = True
                         ) -> List[Union[Prediction, PredictionError]]:
        """Per-item error mode for coalesced batches: out-of-scope rows
        come back as their own :class:`PredictionError` (position
        preserved) while every in-scope row still gets its
        :class:`Prediction` — and the whole batch still costs one
        compiled evaluation.  A daemon maps element *i* back to caller
        *i*; one bad request never fails its batch-mates."""
        preds, errors = self._predict(counts_rows, kernel_names,
                                      model=model, strict=strict,
                                      partial=True)
        return [errors.get(i, p) for i, p in enumerate(preds)]

    def _predict(self, counts_rows, kernel_names, *, model, strict,
                 partial):
        if len(counts_rows) != len(kernel_names):
            raise ValueError(f"{len(kernel_names)} names for "
                             f"{len(counts_rows)} count rows")
        fit_name, mf, m = self.resolve(model)
        unmodeled = [m.unmodeled_features(c) for c in counts_rows]
        errors: Dict[int, PredictionError] = {}
        if strict:
            violations = [scope_violation(i, kname, extra)
                          for i, (kname, extra)
                          in enumerate(zip(kernel_names, unmodeled))
                          if extra]
            if violations:
                if not partial:
                    raise scope_violation_error(fit_name, violations)
                errors = {v["index"]:
                          scope_violation_error(fit_name, [v])
                          for v in violations}

        aligned = m.align(counts_rows)          # counts: absent == 0
        dt = _param_dtype()
        p_vec = jnp.asarray([mf.params[n] for n in m.param_names], dt)
        # compile attrs (repro.spans) land here, one compile per row count
        with spans.span("price.eval", rows=len(aligned)):
            parts = self._evaluator(m)(p_vec, jnp.asarray(aligned, dt))
        with self._lock:
            self.eval_calls += 1
        preds = assemble_predictions(
            kernel_names=list(kernel_names),
            fit_name=fit_name,
            labels=m.breakdown_labels,
            parts=parts,
            feature_names=m.feature_names,
            aligned=aligned,
            unmodeled=unmodeled,
            params=mf.params,
            diagnostics=self.diagnostics_for(fit_name, mf, m),
        )
        return preds, errors

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _evaluator(self, model: Model) -> Callable:
        sig = model.signature()
        with self._lock:
            fn = self._compiled.get(sig)
            if fn is None:
                def parts_fn(p_vec, F, _model=model):
                    # the Python body runs only while jax traces — this
                    # counter IS the trace-count probe tests assert
                    # against
                    self._bump_trace()
                    return _model.batched_breakdown(p_vec, F)

                fn = jax.jit(parts_fn)
                self._compiled[sig] = fn
        return fn

    def _bump_trace(self) -> None:
        # called from inside a jit trace; the compile lock is NOT held
        with self._lock:
            self.trace_count += 1

    def diagnostics_for(self, fit_name: str, mf: ModelFit, m: Model
                        ) -> Dict[str, Any]:
        with self._lock:
            diag = self._fit_diag.get(fit_name)
        if diag is None:
            diag = {
                "fingerprint": self.profile.fingerprint.id,
                "signature": mf.signature,
                "residual_norm": mf.fit.residual_norm,
                "iterations": mf.fit.iterations,
                "converged": mf.fit.converged,
                "trials": self.profile.trials,
                "holdout_gmre": None,
            }
            holdout = self.profile.holdout
            if holdout is not None and len(holdout):
                try:
                    diag["holdout_gmre"] = gmre_of(
                        relative_errors(m, mf.params, holdout))
                    diag["holdout_noise"] = holdout.noise_summary()
                except ValueError:
                    pass        # holdout lacks this model's columns
            with self._lock:
                self._fit_diag[fit_name] = diag
        return diag
