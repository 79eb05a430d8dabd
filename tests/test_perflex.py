"""Unit + property tests for the paper's core: features, models, calibration,
overlap, symbolic counts."""
from repro.testing.proptest import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibrate import (
    fit_model,
    geometric_mean_relative_error,
    levenberg_marquardt,
)
from repro.core.counting import count_fn, parametric_counts
from repro.core.model import Model
from repro.core.overlap import overlap2, overlap3, smooth_step, smoothmax
from repro.core.symbolic import Poly, interpolate_polynomial


# ---------------------------------------------------------------------------
# counting
# ---------------------------------------------------------------------------


def test_matmul_counts_exact():
    c = count_fn(lambda a, b: a @ b, jnp.zeros((32, 48)), jnp.zeros((48, 16)))
    assert c["f_op_float32_madd"] == 32 * 48 * 16


def test_scan_counts_multiply():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    c = count_fn(f, jnp.zeros((16, 16)), jnp.zeros((16, 16)))
    assert c["f_op_float32_madd"] == 7 * 16 ** 3
    assert c["f_op_float32_transc"] == 7 * 16 * 16


def test_integer_pow_charges_square_and_multiply():
    """x**p is floor(log2|p|) squarings + popcount(|p|)−1 extra multiplies
    per element (square-and-multiply lowering), not one and not |p|−1;
    |p| ≤ 1 is a free copy and negative exponents add one divide."""
    for p, muls in [(2, 1), (3, 2), (4, 2), (5, 3), (7, 4), (8, 3),
                    (9, 4), (11, 5), (-2, 1), (-8, 3)]:
        c = count_fn(lambda x, _p=p: x ** _p, jnp.ones((16,)))
        assert c["f_op_float32_mul"] == 16 * muls, (p, dict(c))
        assert c["f_op_float32_div"] == (16 if p < 0 else 0), (p, dict(c))
    for p in (0, 1, -1):
        c = count_fn(lambda x, _p=p: jax.lax.integer_pow(x, _p),
                     jnp.ones((16,)))
        assert c["f_op_float32_mul"] == 0, (p, dict(c))
    c = count_fn(lambda x: jax.lax.integer_pow(x, -1), jnp.ones((16,)))
    assert c["f_op_float32_div"] == 16
    # jnp.square lowers to its own `square` primitive: one mul per
    # element, consistent with x**2 / x*x
    c = count_fn(lambda x: jnp.square(x), jnp.ones((16,)))
    assert c["f_op_float32_mul"] == 16


def test_cond_counts_average():
    def f(x):
        return jax.lax.cond(x.sum() > 0, lambda v: v @ v, lambda v: v, x)

    c = count_fn(f, jnp.zeros((8, 8)))
    assert c["f_op_float32_madd"] == 8 ** 3 / 2  # averaged over branches


def test_collective_counts():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("i",))

    def f(x):
        return jax.lax.psum(x, axis_name="i")

    c = count_fn(
        jax.shard_map(f, mesh=mesh, in_specs=P("i"), out_specs=P()),
        jnp.zeros((8, 4)))
    assert c["f_coll_psum_bytes"] == 8 * 4 * 4


# ---------------------------------------------------------------------------
# symbolic polynomial reconstruction
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(1, 6), st.integers(0, 5), st.integers(0, 7))
@hypothesis.settings(max_examples=25, deadline=None)
def test_poly_interpolation_exact(a, b, c):
    f = lambda n: a * n ** 2 + b * n + c
    p = interpolate_polynomial(lambda n: float(f(n)), {"n": 2})
    for probe in (16, 48, 160, 1024):
        assert p(n=probe) == f(probe)


def test_parametric_counts_match_direct():
    sym = parametric_counts(
        lambda n: (jnp.zeros((n, n)), jnp.zeros((n, n))),
        lambda a, b: jnp.tanh(a @ b), {"n": 3})
    for n in (32, 64, 256):
        direct = count_fn(lambda a, b: jnp.tanh(a @ b),
                          jnp.zeros((n, n)), jnp.zeros((n, n)))
        at = sym.at(n=n)
        for k, v in direct.items():
            assert at[k] == pytest.approx(v), (k, n)


def test_parametric_counts_probe_full_grid_before_freezing_features():
    """A feature absent at the base probe size but present at larger grid
    sizes (a scan that vanishes when n == tile) must still get a
    polynomial — the old code froze the feature set after one probe and
    silently evaluated such features to 0."""

    def fn(x):
        n = x.shape[0]
        if n <= 16:                 # base size: no scan at all
            return x

        def body(c, _):
            return jnp.tanh(c), None

        c, _ = jax.lax.scan(body, x, None, length=n // 16 - 1)
        return c

    sym = parametric_counts(lambda n: (jnp.zeros((n,)),), fn, {"n": 2})
    assert "f_op_float32_transc" in sym.counts
    # transc count is n·(n/16 − 1) = n²/16 − n on the probed lattice
    assert sym.at(n=64)["f_op_float32_transc"] == 64 * 3
    assert sym.at(n=96)["f_op_float32_transc"] == 96 * 5
    assert sym.at(n=16)["f_op_float32_transc"] == 0
    # the scan's loop-step bookkeeping reconstructs too
    assert sym.at(n=64)["f_sync_loop_steps"] == 3


@hypothesis.given(st.lists(st.integers(-5, 5), min_size=1, max_size=4),
                  st.integers(1, 20), st.integers(1, 20))
@hypothesis.settings(max_examples=30, deadline=None)
def test_poly_algebra(coeffs, x, y):
    n = Poly.var("n")
    p = Poly.const(0)
    for i, c in enumerate(coeffs):
        p = p + Poly.const(c) * n ** i
    direct = sum(c * x ** i for i, c in enumerate(coeffs))
    assert p(n=x) == direct
    q = p * p
    assert q(n=y) == (sum(c * y ** i for i, c in enumerate(coeffs))) ** 2


# ---------------------------------------------------------------------------
# model expressions + calibration
# ---------------------------------------------------------------------------


def test_model_parse_and_names():
    m = Model("f_wall_time_x", "p_a * f_op_float32_madd + p_b")
    assert m.param_names == ["p_a", "p_b"]
    assert m.feature_names == ["f_op_float32_madd"]
    with pytest.raises(ValueError):
        Model("f_t", "__import__('os')")
    with pytest.raises(ValueError):
        Model("f_t", "q_bad * f_x")


@hypothesis.given(
    st.lists(st.floats(1e-12, 1e-8), min_size=2, max_size=2),
)
@hypothesis.settings(max_examples=15, deadline=None)
def test_linear_calibration_recovers_params(true_p):
    m = Model("f_wall_time_x", "p_a * f_x + p_b * f_y")
    rows = []
    for n in (64, 96, 128, 192, 256):
        fx, fy = float(n ** 3), float(n ** 2)
        rows.append({"f_x": fx, "f_y": fy,
                     "f_wall_time_x": true_p[0] * fx + true_p[1] * fy})
    fit = fit_model(m, rows, nonneg=True)
    assert fit.params["p_a"] == pytest.approx(true_p[0], rel=0.05)


def test_nonneg_enforced():
    # data generated with a NEGATIVE coefficient: nonneg fit must clamp ≥ 0
    m = Model("f_wall_time_x", "p_a * f_x + p_b * f_y")
    rows = [{"f_x": float(n), "f_y": float(n * n),
             "f_wall_time_x": max(-1e-9 * n + 1e-9 * n * n, 1e-12)}
            for n in (8, 16, 32, 64)]
    fit = fit_model(m, rows, nonneg=True)
    assert fit.params["p_a"] >= 0 and fit.params["p_b"] >= 0


def test_nonneg_by_name_clamps_only_the_named_params():
    # the same negative truth: clamping p_b alone leaves p_a free to fit
    # its negative value, while p_b stays a cost
    m = Model("f_wall_time_x", "p_a * f_x + p_b * f_y")
    rows = [{"f_x": float(n), "f_y": float(n * n),
             "f_wall_time_x": 2e-9 * n * n - 1e-9 * n}
            for n in (8, 16, 32, 64)]
    fit = fit_model(m, rows, nonneg=("p_b",))
    assert fit.params["p_a"] == pytest.approx(-1e-9, rel=1e-3)
    assert fit.params["p_b"] == pytest.approx(2e-9, rel=1e-3)
    fit = fit_model(m, rows, nonneg=("p_a",))
    assert fit.params["p_a"] >= 0


def test_overlap_model_recovers_max_behavior():
    m = Model("f_wall_time_x",
              "overlap2(p_g * f_g, p_c * f_c, p_edge)")
    pg, pc = 1e-9, 4e-9
    rows = []
    # plenty of samples on both plateaus anchor the two rates; a few near
    # the crossover exercise the switch
    for fg, fc in [(1e6, 0), (2e6, 0), (4e6, 1e4), (1e6, 1e5), (2e6, 1e5),
                   (1e6, 5e5), (1e6, 1e6), (1e6, 4e6), (1e6, 1e7),
                   (1e6, 4e7), (2e6, 4e7)]:
        rows.append({"f_g": fg, "f_c": fc,
                     "f_wall_time_x": max(pg * fg, pc * fc)})
    fit = fit_model(m, rows)
    pred = [float(m.evaluate(fit.params, r)) for r in rows]
    meas = [r["f_wall_time_x"] for r in rows]
    # the tanh step smooths the exact max() kink: single-digit-% overall,
    # tight away from the crossover (paper §7.4 quality)
    assert geometric_mean_relative_error(pred, meas) < 0.10
    assert abs(pred[-1] - meas[-1]) / meas[-1] < 0.05   # compute-dominated
    assert abs(pred[0] - meas[0]) / meas[0] < 0.15      # memory-dominated


# ---------------------------------------------------------------------------
# overlap primitives
# ---------------------------------------------------------------------------


@hypothesis.given(st.floats(1e-6, 1.0), st.floats(1e-6, 1.0))
@hypothesis.settings(max_examples=40, deadline=None)
def test_overlap2_approaches_max(a, b):
    got = float(overlap2(a, b, 1e4))
    assert got == pytest.approx(max(a, b), rel=1e-2, abs=1e-4)


@hypothesis.given(st.floats(1e-3, 1.0), st.floats(1e-3, 1.0),
                  st.floats(1e-3, 1.0))
@hypothesis.settings(max_examples=40, deadline=None)
def test_smoothmax_bounds(a, b, c):
    sm = float(smoothmax([a, b, c], 200.0))
    assert sm >= max(a, b, c) - 1e-6
    assert sm <= max(a, b, c) + np.log(3) / 200.0 + 1e-6


def test_smooth_step_limits():
    assert float(smooth_step(1.0, 1e3)) == pytest.approx(1.0, abs=1e-6)
    assert float(smooth_step(-1.0, 1e3)) == pytest.approx(0.0, abs=1e-6)
    assert float(smooth_step(0.0, 1e3)) == pytest.approx(0.5)


def test_levenberg_marquardt_rosenbrock():
    def resid(p):
        return jnp.stack([10.0 * (p[1] - p[0] ** 2), 1.0 - p[0]])

    p, rn, it, conv = levenberg_marquardt(resid, jnp.asarray([-1.2, 1.0]))
    assert rn < 1e-4
    assert np.allclose(np.asarray(p), [1.0, 1.0], atol=1e-2)


def _dot_precisions(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn.params["precision"]
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _dot_precisions(getattr(inner, "jaxpr", inner))


def test_lm_normal_equations_run_at_highest_precision():
    """A TPU runs default-precision f32 matmuls as bf16 passes; the
    solver's J^T J and J^T r must ask for full precision so a fit on the
    chip matches the same fit on the host."""
    from repro.core.calibrate import _lm_core

    x = jnp.linspace(1.0, 2.0, 16)
    jaxpr = jax.make_jaxpr(lambda p: _lm_core(
        lambda q: q[0] * x + q[1] - (3.0 * x + 1.0), p, max_iters=5,
        lam0=1e-3, lam_up=10.0, lam_down=0.3, tol=1e-12, nonneg=True,
    ))(jnp.ones(2))
    precisions = list(_dot_precisions(jaxpr.jaxpr))
    assert len(precisions) >= 2
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(p == highest for p in precisions), precisions


# ---------------------------------------------------------------------------
# batched engine: parity with the reference implementation + feature tables
# ---------------------------------------------------------------------------


def _linear_fixture():
    m = Model("f_wall_time_x", "p_a * f_x + p_b * f_y")
    true_p = (3e-9, 7e-10)
    rows = []
    for n in (64, 96, 128, 192, 256):
        fx, fy = float(n ** 3), float(n ** 2)
        rows.append({"f_x": fx, "f_y": fy,
                     "f_wall_time_x": true_p[0] * fx + true_p[1] * fy})
    return m, rows


def _overlap_fixture():
    m = Model("f_wall_time_x", "overlap2(p_g * f_g, p_c * f_c, p_edge)")
    pg, pc = 1e-9, 4e-9
    rows = []
    for fg, fc in [(1e6, 0), (2e6, 0), (4e6, 1e4), (1e6, 1e5), (2e6, 1e5),
                   (1e6, 5e5), (1e6, 1e6), (1e6, 4e6), (1e6, 1e7),
                   (1e6, 4e7), (2e6, 4e7)]:
        rows.append({"f_g": fg, "f_c": fc,
                     "f_wall_time_x": max(pg * fg, pc * fc)})
    return m, rows


@pytest.mark.parametrize("fixture,nonneg",
                         [(_linear_fixture, True), (_overlap_fixture, False)])
def test_batched_fit_matches_reference_engine(fixture, nonneg):
    """The jitted vmap-of-while-loop engine must reproduce the original
    row-by-row implementation's parameters to 1e-4 relative."""
    from repro.core.calibrate_reference import reference_fit_model

    model, rows = fixture()
    ref_params, _ = reference_fit_model(model, rows, nonneg=nonneg)
    fit = fit_model(model, rows, nonneg=nonneg)
    for n, v in ref_params.items():
        assert fit.params[n] == pytest.approx(v, rel=1e-4, abs=1e-30), n


def test_feature_table_and_rows_agree():
    from repro.core.model import FeatureTable

    model, rows = _linear_fixture()
    table = FeatureTable.from_rows(rows)
    assert table.rows()[0]["f_x"] == rows[0]["f_x"]
    fit_rows = fit_model(model, rows, nonneg=True)
    fit_tab = fit_model(model, table, nonneg=True)
    assert fit_tab.params == fit_rows.params


def test_batched_eval_matches_rowwise_evaluate():
    model, rows = _overlap_fixture()
    params = {"p_g": 1.3e-9, "p_c": 3.7e-9, "p_edge": 55.0}
    from repro.core.model import FeatureTable
    table = FeatureTable.from_rows(rows)
    F = np.stack([table.column(n) for n in model.feature_names], axis=1)
    p_vec = jnp.asarray([params[n] for n in model.param_names])
    batched = np.asarray(model.batched_eval(p_vec, jnp.asarray(F)))
    rowwise = np.asarray([float(model.evaluate(params, r)) for r in rows])
    np.testing.assert_allclose(batched, rowwise, rtol=1e-6)


def test_nonpositive_output_raises_named_valueerror():
    model, rows = _linear_fixture()
    rows[2] = dict(rows[2], f_wall_time_x=0.0, _kernel="bad_kernel")
    with pytest.raises(ValueError, match="bad_kernel"):
        model.residual_fn(rows)
    with pytest.raises(ValueError, match="row 2"):
        model.residual_fn([dict(r, _kernel="") if i == 2 else r
                           for i, r in enumerate(rows)])


# ---------------------------------------------------------------------------
# expression-parser properties: round trip, rejection, batched ≡ row-wise
# ---------------------------------------------------------------------------


def _random_expr(rng, depth=0):
    """Random well-formed model expression from the allowed grammar.

    Returns ``(expr_str, ref_eval)`` where ``ref_eval(env)`` is an
    independent float64 evaluator built alongside the string — the parser
    round-trip oracle.  Only bounded functions (tanh, sqrt∘abs) appear, so
    values stay finite in float32 and comparisons are meaningful.
    """
    r = rng.rand()
    if depth >= 3 or r < 0.35:
        k = rng.randint(3)
        if k == 0:
            n = f"p_{'abc'[rng.randint(3)]}"
            return n, (lambda env, n=n: env[n])
        if k == 1:
            n = f"f_{'xyz'[rng.randint(3)]}"
            return n, (lambda env, n=n: env[n])
        c = round(float(rng.uniform(0.5, 2.0)), 4)
        return repr(c), (lambda env, c=c: c)
    if r < 0.80:
        op = "+-*"[rng.randint(3)]
        a_s, a_f = _random_expr(rng, depth + 1)
        b_s, b_f = _random_expr(rng, depth + 1)
        fn = {"+": lambda x, y: x + y, "-": lambda x, y: x - y,
              "*": lambda x, y: x * y}[op]
        return f"({a_s} {op} {b_s})", \
            (lambda env, a=a_f, b=b_f, fn=fn: fn(a(env), b(env)))
    if r < 0.90:
        a_s, a_f = _random_expr(rng, depth + 1)
        return f"(-{a_s})", (lambda env, a=a_f: -a(env))
    if r < 0.95:
        a_s, a_f = _random_expr(rng, depth + 1)
        return f"tanh({a_s})", (lambda env, a=a_f: float(np.tanh(a(env))))
    a_s, a_f = _random_expr(rng, depth + 1)
    return f"sqrt(abs({a_s}))", \
        (lambda env, a=a_f: float(np.sqrt(np.abs(a(env)))))


@hypothesis.given(st.integers(0, 2 ** 31 - 1))
@hypothesis.settings(max_examples=40, deadline=None)
def test_parser_roundtrip_of_generated_expressions(seed):
    """Any expression from the allowed grammar parses; discovered names
    match the generator's leaves; evaluation matches an independently
    built reference evaluator."""
    import ast

    rng = np.random.RandomState(seed)
    expr, ref = _random_expr(rng)
    m = Model("f_wall_time_x", expr)
    assert m.expr == expr
    names = {n.id for n in ast.walk(ast.parse(expr, mode="eval"))
             if isinstance(n, ast.Name)} - {"tanh", "sqrt", "abs"}
    assert set(m.param_names) == {n for n in names if n.startswith("p_")}
    assert set(m.feature_names) == {n for n in names if n.startswith("f_")}
    assert m.signature() == Model("f_wall_time_x", expr).signature()

    env = {f"p_{c}": 0.5 + 0.25 * i for i, c in enumerate("abc")}
    feats = {f"f_{c}": 0.75 + 0.5 * i for i, c in enumerate("xyz")}
    got = float(m.evaluate(env, feats))
    want = ref({**env, **feats})
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)


# every disallowed AST node class that can appear in an eval-mode parse,
# with an expression exercising it
_DISALLOWED = [
    ("p_a < f_x", "Compare"),
    ("p_a and f_x", "BoolOp"),
    ("p_a if f_x else p_b", "IfExp"),
    ("p_a[0]", "Subscript"),
    ("p_a[0:1]", "Slice"),
    ("p_a.real", "Attribute"),
    ("lambda: p_a", "Lambda"),
    ("{}", "Dict"),
    ("{p_a}", "Set"),
    ("[p_a]", "List"),
    ("[p_a for p_a in f_x]", "ListComp"),
    ("{p_a for p_a in f_x}", "SetComp"),
    ("{p_a: p_a for p_a in f_x}", "DictComp"),
    ("(p_a for p_a in f_x)", "GeneratorExp"),
    ("(p_a, *f_x)", "Starred"),
    ("(p_a := 1.0)", "NamedExpr"),
    ("p_a % f_x", "Mod"),
    ("p_a // f_x", "FloorDiv"),
    ("p_a @ f_x", "MatMult"),
    ("p_a | f_x", "BitOr"),
    ("p_a & f_x", "BitAnd"),
    ("p_a ^ f_x", "BitXor"),
    ("p_a << f_x", "LShift"),
    ("p_a >> f_x", "RShift"),
    ("~p_a", "Invert"),
    ("not p_a", "Not"),
    ("f''", "JoinedStr"),
]


@pytest.mark.parametrize("expr,node_name", _DISALLOWED,
                         ids=[n for _, n in _DISALLOWED])
def test_parser_rejects_every_disallowed_node_class(expr, node_name):
    import ast

    node_cls = getattr(ast, node_name)
    from repro.core.model import _ALLOWED_NODES
    assert not issubclass(node_cls, _ALLOWED_NODES)
    # the expression really exercises that node class...
    tree = ast.parse(expr, mode="eval")
    assert any(isinstance(n, node_cls) for n in ast.walk(tree)), node_name
    # ...and the model parser refuses it
    with pytest.raises(ValueError):
        Model("f_t", expr)


def test_parser_rejects_unknown_functions_and_non_name_calls():
    with pytest.raises(ValueError, match="unknown function"):
        Model("f_t", "nosuchfn(p_a)")
    with pytest.raises(ValueError):
        Model("f_t", "(p_a)(f_x)")


@hypothesis.given(st.integers(0, 2 ** 31 - 1), st.integers(1, 12))
@hypothesis.settings(max_examples=25, deadline=None)
def test_batched_eval_equals_rowwise_on_random_tables(seed, n_rows):
    """batched_eval over a random feature table ≡ row-by-row evaluate, for
    random grammar-generated models."""
    from repro.core.model import FeatureTable

    rng = np.random.RandomState(seed)
    expr, _ = _random_expr(rng)
    m = Model("f_wall_time_x", expr)
    params = {n: float(rng.uniform(0.1, 3.0)) for n in m.param_names}
    rows = [{n: float(rng.uniform(0.1, 3.0)) for n in m.feature_names}
            for _ in range(n_rows)]
    table = FeatureTable.from_rows(rows)

    if m.feature_names:
        F = np.stack([table.column(n) for n in m.feature_names], axis=1)
    else:
        F = np.zeros((n_rows, 0))
    p_vec = jnp.asarray([params[n] for n in m.param_names], jnp.float32)
    batched = np.asarray(m.batched_eval(p_vec, jnp.asarray(F, jnp.float32)))
    rowwise = np.asarray([float(m.evaluate(params, r)) for r in rows])
    assert batched.shape == (n_rows,)
    np.testing.assert_allclose(batched, rowwise, rtol=1e-5, atol=1e-7)


def test_singular_system_recovers_via_damping():
    """A rank-deficient Jacobian (duplicated feature column) must not blow
    up: non-finite solves bump damping inside the trace and the fit still
    lands on the data."""
    m = Model("f_wall_time_x", "p_a * f_x + p_b * f_x")  # perfectly collinear
    rows = [{"f_x": float(n), "f_wall_time_x": 2e-9 * n}
            for n in (8, 16, 32, 64)]
    fit = fit_model(m, rows, nonneg=True)
    pred = [float(m.evaluate(fit.params, r)) for r in rows]
    meas = [r["f_wall_time_x"] for r in rows]
    assert geometric_mean_relative_error(pred, meas) < 1e-3
