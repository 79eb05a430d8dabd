"""The static modelability auditor (``repro.analysis``).

Fixture kernels with KNOWN defects must each draw exactly the diagnostic
class built for that defect — and drawing it must cost abstract traces
only (no kernel execution, no device allocation, no timing):

* scope: unmodeled/opaque primitives, data-dependent while loops,
  mixed precision, runtime-indexed access;
* families: declared FamilySpec degrees checked by exact finite
  differencing over the probe lattice, plus lattice divisibility;
* identifiability: design-matrix rank defects named per parameter;
* signature hazards: callables the count store can never dedup;
* the run_study gate: unidentifiable zoo rungs refuse to fit without
  ``force=True``;
* count-store GC: corrupt > schema > age precedence, foreign files
  untouched.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    AnalysisError,
    Diagnostic,
    DiagnosticReport,
    abstract_args,
    analyze_model,
    audit_callable,
    audit_signature,
    check_lattice,
    load_baseline,
    save_baseline,
    validate_family,
)
from repro.analysis.diagnostics import sort_key
from repro.core.countengine import COUNT_STORE_VERSION, CountEngine
from repro.core.model import Model
from repro.core.uipick import (
    FamilySpec,
    Generator,
    LatticeAssumptionWarning,
    MeasurementKernel,
)

X64 = jax.ShapeDtypeStruct((64,), jnp.float32)


def _codes(diags):
    return sorted({d.code for d in diags})


# ---------------------------------------------------------------------------
# scope auditor
# ---------------------------------------------------------------------------


def test_unmodeled_primitive_is_an_error():
    diags = audit_callable(lambda x: jnp.cumprod(x), (X64,), "kernel:cp")
    assert _codes(diags) == ["unmodeled-primitive"]
    d = diags[0]
    assert d.severity == "error"
    assert d.details["primitive"] == "cumprod"


def test_opaque_primitive_callback_is_an_error():
    def fn(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    diags = audit_callable(fn, (X64,), "kernel:cb")
    assert "opaque-primitive" in _codes(diags)
    assert all(d.severity == "error" for d in diags
               if d.code == "opaque-primitive")


def test_data_dependent_while_is_a_warning():
    def fn(x):
        return jax.lax.while_loop(
            lambda c: c[1] < 5, lambda c: (c[0] * 1.5, c[1] + 1), (x, 0))[0]

    diags = audit_callable(fn, (X64,), "kernel:wh")
    assert _codes(diags) == ["while-trip-count"]
    assert diags[0].severity == "warning"


def test_mixed_precision_is_a_warning_naming_both_dtypes():
    def fn(x):
        return (x.astype(jnp.bfloat16) * 2).astype(jnp.float32) + x * 3

    diags = audit_callable(fn, (X64,), "kernel:mp")
    assert _codes(diags) == ["mixed-precision"]
    assert diags[0].details["dtypes"] == ["bfloat16", "float32"]


def test_runtime_indexing_is_an_info():
    def fn(x):
        return jnp.take(x, jnp.zeros((4,), jnp.int32))

    diags = audit_callable(fn, (X64,), "kernel:tk")
    assert _codes(diags) == ["data-dependent-access"]
    assert diags[0].severity == "info"


def test_untraceable_kernel_is_reported_not_raised():
    stats = {"traces": 0}
    diags = audit_callable(lambda x: x.no_such_attr(), (X64,),
                           "kernel:boom", stats=stats)
    assert _codes(diags) == ["untraceable-kernel"]
    assert stats["traces"] == 1     # the failed attempt still counts


def test_clean_kernel_draws_nothing():
    assert audit_callable(lambda x: jnp.tanh(x) + 1.0, (X64,),
                          "kernel:ok") == []


def test_abstract_args_never_materializes_the_arrays():
    """The builder below would allocate 4 TiB if it ever ran concretely;
    eval_shape hands back pure shape/dtype structs instead."""
    def make_args():
        return (jnp.zeros((1 << 20, 1 << 20), jnp.float32),)

    (a,) = abstract_args(make_args)
    assert a.shape == (1 << 20, 1 << 20) and a.dtype == jnp.float32
    assert audit_callable(lambda x: x * 2.0, (a,), "kernel:huge") == []


# ---------------------------------------------------------------------------
# family validator
# ---------------------------------------------------------------------------


def _fixture_kernel(n, shape):
    def fn(x):
        return x * 2.0

    def make_args():
        return (jnp.ones(shape, jnp.float32),)

    return MeasurementKernel(name=f"fx_{n}", fn=fn, make_args=make_args,
                             tags={}, sizes={"n": n})


def _fixture_gen(shape_of, degree, sizes=(16, 32)):
    return Generator("fixture", frozenset({"fx"}),
                     arg_space=dict(n=tuple(sizes)),
                     build=lambda *, n: _fixture_kernel(n, shape_of(n)),
                     family=FamilySpec(var_degrees={"n": degree}))


def test_family_degree_mismatch_quadratic_declared_linear():
    gen = _fixture_gen(lambda n: (n, n), degree=1)
    stats = {"traces": 0}
    diags = validate_family(gen, stats=stats)
    assert "family-degree-mismatch" in _codes(diags)
    d = next(d for d in diags if d.code == "family-degree-mismatch")
    assert d.severity == "error"
    assert d.details["declared_degree"] == 1
    assert d.details["actual_degree"] == 2
    assert stats["traces"] == 4     # d+3 lattice points, memoized


def test_family_non_polynomial_log_factor():
    # element count n·bit_length(n): no polynomial of any degree fits the
    # lattice, so Δ^{d+1} is non-constant
    gen = _fixture_gen(lambda n: (n * int(n).bit_length(),), degree=1)
    diags = validate_family(gen)
    assert "family-non-polynomial" in _codes(diags)
    d = next(d for d in diags if d.code == "family-non-polynomial")
    assert d.severity == "error"
    assert d.details["lattice"] == [16, 32, 48, 64]


def test_family_degree_overdeclared_is_an_info():
    gen = _fixture_gen(lambda n: (n,), degree=2)
    diags = validate_family(gen)
    assert _codes(diags) == ["family-degree-overdeclared"]
    assert diags[0].severity == "info"


def test_family_correct_degree_is_silent():
    assert validate_family(_fixture_gen(lambda n: (n,), degree=1)) == []
    assert validate_family(_fixture_gen(lambda n: (n, n), degree=2)) == []


def test_family_validator_skips_familyless_generators():
    gen = Generator("plain", frozenset({"p"}), arg_space=dict(n=(16,)),
                    build=lambda *, n: _fixture_kernel(n, (n,)))
    assert validate_family(gen) == []
    assert check_lattice(gen) == []


def test_check_lattice_flags_off_lattice_argument_sizes():
    gen = _fixture_gen(lambda n: (n,), degree=1, sizes=(16, 20, 32))
    diags = check_lattice(gen)
    assert _codes(diags) == ["probe-lattice-divisibility"]
    assert diags[0].severity == "warning"
    assert diags[0].details == {"variable": "n", "sizes": [20], "scale": 16}


def test_generation_time_lattice_warning_matches_static_diagnostic():
    """The runtime twin: actually generating the off-lattice variant warns
    LatticeAssumptionWarning once."""
    gen = _fixture_gen(lambda n: (n,), degree=1, sizes=(16, 20))
    with pytest.warns(LatticeAssumptionWarning):
        kernels = list(gen.variants({}))
    assert len(kernels) == 2


# ---------------------------------------------------------------------------
# identifiability analyzer
# ---------------------------------------------------------------------------


def test_collinear_parameters_named_with_shared_features():
    m = Model("f_t", "p_a * f_x + p_b * f_x")
    F = m.align([{"f_x": 1.0}, {"f_x": 2.0}, {"f_x": 3.0}], missing="zero")
    diags = analyze_model(m, F, "model:twin")
    # the pairwise diagnostic names the defect; the generic rank-defect
    # diagnostic must NOT double-report the same pair
    assert _codes(diags) == ["collinear-parameters"]
    d = diags[0]
    assert d.details["params"] == ["p_a", "p_b"]
    assert d.details["features"] == {"p_a": ["f_x"], "p_b": ["f_x"]}


def test_unexercised_parameter_names_its_features():
    m = Model("f_t", "p_a * f_x + p_b * f_y")
    F = m.align([{"f_x": 1.0}, {"f_x": 2.0}], missing="zero")
    diags = analyze_model(m, F, "model:dead")
    assert _codes(diags) == ["unexercised-parameter"]
    assert diags[0].details == {"param": "p_b", "features": ["f_y"]}


def test_underdetermined_battery_fewer_rows_than_params():
    m = Model("f_t", "p_a * f_x + p_b * f_y")
    F = m.align([{"f_x": 1.0, "f_y": 2.0}], missing="zero")
    diags = analyze_model(m, F, "model:thin")
    assert _codes(diags) == ["underdetermined-battery"]
    assert diags[0].details["rows"] == 1


def test_ill_conditioned_fit_full_rank_but_wobbly():
    eps = 1e-6
    m = Model("f_t", "p_a * f_x + p_b * f_y + p_c * f_z")
    rows = [{"f_x": 1.0, "f_y": 0.0, "f_z": 1.0 + eps},
            {"f_x": 0.0, "f_y": 1.0, "f_z": 1.0 + eps},
            {"f_x": 1.0, "f_y": 1.0, "f_z": 2.0 - eps}]
    diags = analyze_model(m, m.align(rows, missing="zero"), "model:wob")
    assert _codes(diags) == ["ill-conditioned-fit"]
    assert diags[0].severity == "warning"
    assert diags[0].details["condition_number"] > 1e6


def test_well_posed_battery_is_silent():
    m = Model("f_t", "p_a * f_x + p_b * f_y")
    rows = [{"f_x": 1.0, "f_y": 0.0}, {"f_x": 0.0, "f_y": 1.0},
            {"f_x": 2.0, "f_y": 3.0}]
    assert analyze_model(m, m.align(rows, missing="zero"), "model:ok") == []


def test_run_study_refuses_unidentifiable_rung_unless_forced():
    from repro.studies import STUDY_SMOKE_TAGS, StudyError, run_study
    from repro.studies.zoo import ZooEntry
    from repro.testing.synthdev import fleet_device

    device = fleet_device("citra", noise=0.0)
    twin = ZooEntry(
        name="twin_madd", scope_rank=0,
        expr="p_a * f_op_float32_madd + p_b * f_op_float32_madd "
             "+ p_launch * f_sync_launch_kernel")
    with pytest.raises(StudyError, match="collinear-parameters"):
        run_study(fingerprint=device.fingerprint, timer=device.timer,
                  tags=STUDY_SMOKE_TAGS, trials=2, entries=[twin])
    profile = run_study(fingerprint=device.fingerprint, timer=device.timer,
                        tags=STUDY_SMOKE_TAGS, trials=2, entries=[twin],
                        force=True)
    assert "twin_madd" in profile.fits


def _zoo_rows(model, n_rows=14, seed=0):
    """A battery-like train split for a zoo rung: each row a random mix of
    matmul, stream and empty-kernel features over six decades."""
    rng = np.random.default_rng(seed)
    F = 10.0 ** rng.uniform(0.0, 6.0, (n_rows, len(model.feature_names)))
    return F * (rng.uniform(size=F.shape) < 0.7)


def _identifiability_cases():
    """(name, model, aligned features): the zoo's three rungs and the
    fixture models above, each with its own battery."""
    from repro.studies.zoo import MODEL_ZOO

    cases = []
    for e in MODEL_ZOO:
        m = e.model()
        cases.append((e.name, m, _zoo_rows(m)))
    fixtures = {
        "twin": ("p_a * f_x + p_b * f_x",
                 [{"f_x": 1.0}, {"f_x": 2.0}, {"f_x": 3.0}]),
        "dead": ("p_a * f_x + p_b * f_y", [{"f_x": 1.0}, {"f_x": 2.0}]),
        "thin": ("p_a * f_x + p_b * f_y", [{"f_x": 1.0, "f_y": 2.0}]),
        "wobbly": ("p_a * f_x + p_b * f_y + p_c * f_z",
                   [{"f_x": 1.0, "f_y": 0.0, "f_z": 1.0 + 1e-6},
                    {"f_x": 0.0, "f_y": 1.0, "f_z": 1.0 + 1e-6},
                    {"f_x": 1.0, "f_y": 1.0, "f_z": 2.0 - 1e-6}]),
    }
    for name, (expr, rows) in fixtures.items():
        m = Model("f_t", expr)
        cases.append((name, m, m.align(rows, missing="zero")))
    return cases


def _eager_param_jacobian(model, points, features):
    """The design matrix as it was computed before it was compiled: an
    eager ``jax.jacfwd`` of ``batched_eval`` at each point."""
    from repro.core.model import _param_dtype

    dt = _param_dtype()
    F = jnp.asarray(features, dt)
    return np.stack([np.asarray(jax.jacfwd(
        lambda p: model.batched_eval(p, F))(jnp.asarray(p, dt)), np.float64)
        for p in points])


def _same_diagnostics(got, want):
    assert [(d.severity, d.code, d.location) for d in got] \
        == [(d.severity, d.code, d.location) for d in want]
    for g, w in zip(got, want):
        assert g.details.keys() == w.details.keys()
        for k, v in w.details.items():
            if isinstance(v, float):
                assert g.details[k] == pytest.approx(v, rel=1e-6), k
            else:
                assert g.details[k] == v, k


@pytest.mark.parametrize(
    "name,model,features",
    [pytest.param(*case, id=case[0]) for case in _identifiability_cases()])
def test_compiled_design_matrix_matches_eager_autodiff(
        name, model, features, monkeypatch):
    from repro.analysis.identifiability import _probe_points

    points = _probe_points(len(model.param_names))
    J = model.param_jacobian(points, features)
    assert J.dtype == np.float64
    assert J.shape == (len(points), len(features), len(model.param_names))
    np.testing.assert_allclose(
        J, _eager_param_jacobian(model, points, features), rtol=1e-6)

    got = analyze_model(model, features, f"model:{name}")
    monkeypatch.setattr(Model, "param_jacobian", _eager_param_jacobian)
    _same_diagnostics(got, analyze_model(model, features, f"model:{name}"))


def test_equal_models_reuse_the_compiled_design_matrix():
    """The second analysis of re-created, equal zoo rungs (as every
    profile of a study makes them) compiles nothing: each rung's
    Jacobian program comes from the process-wide cache."""
    from repro import spans
    from repro.studies.zoo import zoo_models

    def identify(models):
        with spans.span("solve.identify", models=len(models),
                        reused=0) as s:
            diags = [analyze_model(m, _zoo_rows(m, n_rows=17), name)
                     for name, m in sorted(models.items())]
        return s.attrs, diags

    first, want = identify(zoo_models())
    second, got = identify(zoo_models())
    assert second.get("compiles", 0) == 0
    assert second["reused"] == second["models"] == 3
    assert got == want


def test_shared_compiled_cache_is_fifo_bounded(monkeypatch):
    from repro.core import model as model_mod

    monkeypatch.setattr(model_mod, "_SHARED_COMPILED", {})
    monkeypatch.setattr(model_mod, "_SHARED_COMPILED_MAX", 2)
    builds = []

    def build(tag):
        def make():
            builds.append(tag)
            return lambda: tag
        return make

    exprs = [f"p_a * f_x + {c} * p_b * f_y" for c in (1.0, 2.0, 3.0)]
    kept = []
    for i, expr in enumerate(exprs):
        m = Model("f_t", expr)
        fn, built = m.compiled(("k",), build(i))
        assert built and fn() == i
        kept.append((m, fn))
    assert builds == [0, 1, 2]
    assert len(model_mod._SHARED_COMPILED) == 2
    # the oldest went first: an equal model builds it again, while the
    # newer ones are still shared
    for i, expr in ((2, exprs[2]), (0, exprs[0])):
        fn, built = Model("f_t", expr).compiled(("k",), build(i))
        assert built == (i == 0) and fn() == i
    assert builds == [0, 1, 2, 0]
    assert len(model_mod._SHARED_COMPILED) == 2
    # an instance keeps its own programs, whatever the shared cache evicts
    m1, fn1 = kept[1]
    assert (m1.signature(), "k") not in model_mod._SHARED_COMPILED
    assert m1.compiled(("k",), build(1)) == (fn1, False)
    assert builds == [0, 1, 2, 0]


# ---------------------------------------------------------------------------
# cache-signature hazards
# ---------------------------------------------------------------------------


def test_sourceless_callable_is_unsignable():
    ns = {}
    exec("def nosrc(x):\n    return x * 2.0", ns)
    diags = audit_signature(ns["nosrc"], "kernel:nosrc")
    assert _codes(diags) == ["unsignable-callable"]
    assert diags[0].severity == "warning"
    assert any("source" in r for r in diags[0].details["reasons"])


def test_mutable_captured_state_is_an_info():
    cfg = {"k": 2.0}

    def kern(x, opts=[1.0]):            # noqa: B006 — the defect under test
        return x * cfg["k"] * opts[0]

    diags = audit_signature(kern, "kernel:mut")
    assert "mutable-captured-state" in _codes(diags)
    d = next(d for d in diags if d.code == "mutable-captured-state")
    assert d.details["names"] == ["cfg", "opts"]


def test_plain_closure_over_scalars_is_clean():
    c = 3.0

    def kern(x):
        return x * c

    assert audit_signature(kern, "kernel:ok") == []


# ---------------------------------------------------------------------------
# diagnostics: ordering, suppression, baseline
# ---------------------------------------------------------------------------


def _diag(sev, code, loc, msg="m"):
    return Diagnostic(sev, code, loc, msg)


def test_report_sorts_by_severity_then_location_then_code():
    report = DiagnosticReport()
    report.extend([
        _diag("info", "c", "z"),
        _diag("error", "b", "kernel:b"),
        _diag("warning", "a", "kernel:a"),
        _diag("error", "a", "kernel:b"),
        _diag("error", "a", "kernel:a"),
    ])
    got = [(d.severity, d.location, d.code) for d in report.sorted()]
    assert got == [("error", "kernel:a", "a"), ("error", "kernel:b", "a"),
                   ("error", "kernel:b", "b"), ("warning", "kernel:a", "a"),
                   ("info", "z", "c")]
    assert got == [(d.severity, d.location, d.code)
                   for d in sorted(report.diagnostics, key=sort_key)]


def test_invalid_severity_is_rejected():
    with pytest.raises(ValueError, match="severity"):
        Diagnostic("fatal", "c", "l", "m")


def test_suppress_by_code_and_by_key():
    report = DiagnosticReport()
    report.extend([_diag("error", "a", "k:1"), _diag("error", "a", "k:2"),
                   _diag("error", "b", "k:1")])
    by_code = report.suppress(["a"])
    assert [d.code for d in by_code.diagnostics] == ["b"]
    assert len(by_code.suppressed) == 2
    by_key = report.suppress(["a@k:1"])
    assert sorted(d.key for d in by_key.diagnostics) == ["a@k:2", "b@k:1"]
    # suppressed findings never fail the run
    assert by_code.new_errors([]) == by_code.diagnostics


def test_baseline_round_trip_and_regression(tmp_path):
    report = DiagnosticReport()
    report.extend([_diag("error", "a", "k:1"), _diag("warning", "w", "k:1")])
    path = tmp_path / "baseline.json"
    save_baseline(report, path)
    assert load_baseline(path) == ["a@k:1"]     # warnings never baseline
    assert report.new_errors(load_baseline(path)) == []
    report.extend([_diag("error", "a", "k:2")])
    assert [d.key for d in report.new_errors(load_baseline(path))] \
        == ["a@k:2"]


def test_malformed_baseline_is_a_typed_error(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 2, 3]")
    with pytest.raises(AnalysisError, match="lint baseline"):
        load_baseline(bad)
    with pytest.raises(AnalysisError, match="cannot read"):
        load_baseline(tmp_path / "missing.json")


# ---------------------------------------------------------------------------
# count-store GC
# ---------------------------------------------------------------------------


def _stream_kernel(c):
    def fn(x):
        return x * c

    return fn


def _seed_entries(store, n):
    eng = CountEngine(store=store)
    for i in range(n):
        eng.counts_of_callable(_stream_kernel(float(i + 1)),
                               (jnp.ones((8,), jnp.float32),))
    files = sorted((store / "counts").glob("*.json"))
    assert len(files) == n
    return files


def test_gc_precedence_corrupt_then_schema_then_age(tmp_path):
    keep, corrupt, schema, old = _seed_entries(tmp_path, 4)
    # corrupt AND ancient: corrupt wins (precedence)
    corrupt.write_text("not json at all")
    os.utime(corrupt, (1, 1))
    payload = json.loads(schema.read_text())
    payload["version"] = COUNT_STORE_VERSION - 1
    schema.write_text(json.dumps(payload))
    os.utime(old, (1, 1))
    # a foreign file is never ours to delete
    stranger = tmp_path / "counts" / "README.json"
    stranger.write_text("{}")

    stats = CountEngine(store=tmp_path).gc(max_age=3600.0)
    assert (stats.kept, stats.dropped_corrupt, stats.dropped_schema,
            stats.dropped_old) == (1, 1, 1, 1)
    assert stats.dropped == 3
    assert keep.exists() and stranger.exists()
    assert not corrupt.exists() and not schema.exists() and not old.exists()


def test_gc_drops_entries_whose_key_disagrees_with_filename(tmp_path):
    (entry,) = _seed_entries(tmp_path, 1)
    miscopied = entry.with_name("0" * 64 + ".json")
    miscopied.write_text(entry.read_text())
    stats = CountEngine(store=tmp_path).gc()
    assert stats.kept == 1 and stats.dropped_corrupt == 1
    assert entry.exists() and not miscopied.exists()


def test_gc_without_max_age_keeps_valid_entries(tmp_path):
    files = _seed_entries(tmp_path, 2)
    for f in files:
        os.utime(f, (1, 1))
    stats = CountEngine(store=tmp_path).gc()
    assert stats.kept == 2 and stats.dropped == 0
    stats = CountEngine(store=tmp_path).gc(max_age=3600.0)
    assert stats.kept == 0 and stats.dropped_old == 2


def test_gc_on_storeless_engine_is_a_noop():
    stats = CountEngine().gc(max_age=0.0)
    assert stats.kept == 0 and stats.dropped == 0


# ---------------------------------------------------------------------------
# the session facade's audit
# ---------------------------------------------------------------------------


def test_session_audit_flags_out_of_scope_and_unmodeled(tmp_path):
    from repro.api import PerfSession
    from repro.core.calibrate import FitResult
    from repro.profiles import DeviceFingerprint, MachineProfile, ModelFit

    model = Model("f_wall_time_cpu_host",
                  "p_madd * f_op_float32_madd "
                  "+ p_launch * f_sync_launch_kernel")
    fit = FitResult(params={"p_madd": 1e-10, "p_launch": 1e-6},
                    residual_norm=0.0, iterations=1, converged=True)
    profile = MachineProfile(
        fingerprint=DeviceFingerprint(platform="synth",
                                      device_kind="audit-test", n_devices=1),
        fits={"lin": ModelFit.from_fit(model, fit)}, trials=2)
    session = PerfSession.open(profile)

    abstract = (jax.ShapeDtypeStruct((32,), jnp.float32),)
    report = session.audit([
        (lambda x: jnp.tanh(x) * 2.0, abstract),    # transc: out of scope
        (lambda x: jnp.cumprod(x), abstract),       # unmodeled primitive
    ])
    codes = report.codes()
    assert "out-of-scope-feature" in codes
    assert "unmodeled-primitive" in codes
    assert report.stats["timings"] == 0
    assert report.stats["traces"] >= 2
