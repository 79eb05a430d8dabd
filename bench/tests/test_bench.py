"""Tests of the benchmark itself, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests -q

They skip the harness's look for a chip (``require_tpu=False``) and drive
the rest of a run on fixture cells (``bench/tests/fixtures``): a sound run
is correct, the bfloat16 control is not, and neither is a run with the
timed path broken underneath.  The trace reduction is checked on a
synthetic trace worked out by hand and on each trimmed trace recorded on
the chip under ``bench/fixtures``.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import core, reference as R, roofline, trace, traffic  # noqa

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout-shaped tree: the real BENCHMARK.json with two fixture
    cells and the price loop's metrics added, the real bench files, and
    the fixture configs and traffic copied in as new files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind in ("configs", "traffic"):
        for f in (HERE / "fixtures" / kind).glob("*.json"):
            shutil.copy(f, root / "bench" / kind / f.name)
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"] += [
        {"name": "tiny-xlstm", "source": "fixture", "reduced": [],
         "file": "bench/configs/tiny-xlstm.json", "why": "test"},
        {"name": "tiny-zamba2", "source": "fixture", "reduced": [],
         "file": "bench/configs/tiny-zamba2.json", "why": "test"}]
    bm["workloads"] += [
        {"name": "tiny.calibrate", "config": "tiny-xlstm",
         "traffic": "calibrate_tiny", "chips": 1, "why": "test"},
        {"name": "tiny.price", "config": "tiny-zamba2",
         "traffic": "price_tiny", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        w = m.get("workloads")
        if w and "xlstm-125m.calibrate" in w:
            w.append("tiny.calibrate")
    # the price loop's metrics, which no cell of BENCHMARK.json reports yet
    price = json.loads((HERE / "fixtures" / "price_metrics.json").read_text())
    for key in ("end_to_end", "per_layer"):
        bm[key] += price[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def _run(tree, workload, seed=3_000_000_019, seconds=4, trace_on=0,
         control=False):
    from bench import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace_on)],
                      require_tpu=False, bench_dir=tree / "bench",
                      control=control)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None


# ---------------------------------------------------------------------------
# finding things by name; adding a cell is adding files
# ---------------------------------------------------------------------------


def test_files_found_by_name(tree):
    bm = core.load_benchmark(ROOT)
    for cell in bm["workloads"]:
        cfg = core.data_file("configs", cell["config"])
        tr = core.data_file("traffic", cell["traffic"])
        assert cfg["subjects"] and core.loop(tr["kind"])
    for m in bm["per_layer"]:
        assert callable(core.reader(m["name"]))
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
    with pytest.raises(core.BenchError):
        core.data_file("configs", "no-such-config")


def test_new_config_is_new_files_only(tree):
    """The fixture cells exist only as files copied in and entries added;
    the harness runs one without any edit."""
    rc, res = _run(tree, "tiny.calibrate", seconds=2)
    assert rc == 0 and res["correct"], res
    assert list(res) == CONTRACT_KEYS
    assert set(res["metrics"]) == {"setup_s", "profile_s"}
    assert res["checks"] and all(set(c) == {"value", "limit"}
                                 for c in res["checks"].values())


def test_traffic_same_seed_same_requests(tree):
    cfg = core.data_file("configs", "tiny-zamba2", tree / "bench")
    tr = core.data_file("traffic", "price_tiny", tree / "bench")
    cat = traffic.catalog(cfg, tr)
    new = traffic.novel(cfg, tr, cat)
    a = traffic.schedule(tr, cat, new, 10.0, 20.0, 3_000_000_001)
    b = traffic.schedule(tr, cat, new, 10.0, 20.0, 3_000_000_001)
    c = traffic.schedule(tr, cat, new, 10.0, 20.0, 7)
    assert [(d, i.label) for d, i in a] == [(d, i.label) for d, i in b]
    # another seed: the same requests at the same times, each within a
    # block of arrivals, in another order
    assert [d for d, _ in a] == [d for d, _ in c]
    assert [i.label for _, i in a] != [i.label for _, i in c]
    block = tr["shuffle_block"]
    for lo in range(0, len(a), block):
        assert sorted(i.label for _, i in a[lo:lo + block]) \
            == sorted(i.label for _, i in c[lo:lo + block])
    assert max(d for d, _ in a) < 10.0
    kinds = [i.kind for _, i in a]
    for kind, share in tr["mix"].items():
        assert abs(kinds.count(kind) / len(a) - share) < 0.01
    labels = {i.label for v in cat.values() for i in v}
    assert sum(i.label not in labels for _, i in a) == 4


def test_no_tpu_exits_without_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "xlstm-125m.calibrate", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "xlstm-125m.calibrate", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ---------------------------------------------------------------------------
# correct: sound, the control, and the timed path broken underneath
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", ["tiny.calibrate", "tiny.price"])
def test_sound_run_is_correct_and_control_is_not(tree, workload):
    rc, sound = _run(tree, workload)
    assert rc == 0 and sound["correct"], sound["checks"]
    rc, ctl = _run(tree, workload, control=True)
    assert rc == 0 and not ctl["correct"]
    assert ctl["checks"]["price_gap"]["value"] \
        > 3 * sound["checks"]["price_gap"]["value"]


def _fit_faults():
    from repro.studies import study

    orig = study.fit_models

    def unchanged(models, table, **kw):
        fits = orig(models, table, **kw)
        for f in fits.values():
            f.params = {k: 1e-9 for k in f.params}
        return fits

    def half(models, table, **kw):
        return orig(models, table.select(list(range(0, len(table), 2))),
                    **kw)

    return study, {"state_unchanged": unchanged, "half_batch": half}


def _altered():
    """The first price of every evaluation made 1% dearer."""
    from repro.api import engine

    orig = engine.assemble_predictions

    def altered(**kw):
        preds = orig(**kw)
        p = preds[0]
        preds[0] = type(p)(**{**p.__dict__, "seconds": p.seconds * 1.01})
        return preds

    return engine, "assemble_predictions", altered


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_calibrate_fault_is_not_correct(tree, monkeypatch, fault):
    if fault == "answer_altered":
        monkeypatch.setattr(*_altered())
    else:
        study, faults = _fit_faults()
        monkeypatch.setattr(study, "fit_models", faults[fault])
    rc, res = _run(tree, "tiny.calibrate", seconds=2)
    assert rc == 0 and not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_price_fault_is_not_correct(tree, monkeypatch, fault):
    if fault == "answer_altered":
        monkeypatch.setattr(*_altered())
    else:
        from repro.serving import coalesce

        orig = coalesce.CoalescingBatcher._execute

        def half(self, batch):
            return orig(self, batch[:max(1, len(batch) // 2)])

        monkeypatch.setattr(coalesce.CoalescingBatcher, "_execute", half)
    rc, res = _run(tree, "tiny.price", seconds=3)
    assert rc == 0 and not res["correct"], res["checks"]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def test_reference_counts_agree_with_closed_forms():
    import jax
    import jax.numpy as jnp

    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    c = R.count(lambda x, y: (x @ y).sum(), (a, b))
    assert c[R.MADD] == 64 * 32 * 16
    assert c[R.LOAD] == 64 * 32 + 32 * 16 and c[R.STORE] == 64 * 16
    assert c[R.ADD] == 64 * 16 and c[R.LAUNCH] == 1
    scan = R.count(lambda x: jax.lax.scan(
        lambda s, _: (s + 1.0, None), x, None, length=7)[0],
        (jax.ShapeDtypeStruct((10,), jnp.float32),))
    assert scan[R.ADD] == 70


def test_reference_fit_recovers_linear_rates():
    rng = np.random.default_rng(0)
    counts = {f: rng.uniform(1e6, 1e9, 40) for f in R.FEATURES}
    counts[R.LAUNCH] = np.ones(40)
    truth = {"p_madd": 2e-13, "p_mem": 3e-12, "p_launch": 5e-4}
    t = R.price("lin_flop_mem", truth, counts)
    got = R.fit("lin_flop_mem", counts, t)
    for k, v in truth.items():
        assert abs(got[k] - v) / v < 1e-9
    assert R.cost("lin_flop_mem", got, counts, t) < 1e-20


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on the chip
# ---------------------------------------------------------------------------


def _synthetic():
    """A made-up trace in the reduced form: two kernels' timing spans in a
    window, each program execution with its ops, and host gaps."""
    ops, modules, spans = [], [], [["bench.window", 0, 20_000_000]]
    t = 100_000
    for k, (name, dur) in enumerate((("matmul_sq_n512_float32_pfFalse_t16",
                                      40_000),
                                     ("stream_contig_n1048576_a2_float32",
                                      25_000))):
        start = t
        for call in range(11):
            modules.append(["jit_fn(%d)" % k, t, dur])
            ops.append([f"fusion.{k}", t, dur // 2])
            ops.append([f"copy.{k}", t + dur // 4, dur // 2])   # overlaps
            t += dur + 500_000
        spans.append([f"bench.kernel:{name}", start, t - start])
        t += 1_000_000
    trace_ = {"ops": {"/device:TPU:0": ops},
              "modules": {"/device:TPU:0": modules}, "spans": spans}
    rows = [{"name": "bench.window", "start": 0.0, "end": 0.02}]
    for n, s0, d in spans[1:]:
        kernel = n.split(":", 1)[1]
        tags = ({"n": 512, "dtype": "float32"} if kernel.startswith("mat")
                else {"nelements": 1048576, "n_arrays": 2,
                      "dtype": "float32"})
        rows.append({"name": n, "start": s0 / 1e9, "end": (s0 + d) / 1e9,
                     "kernel": kernel, "tags": tags, "module": "jit_fn"})
    return trace_, rows


#: the synthetic trace, and each trimmed chip trace under bench/fixtures
TRACES = ["synthetic"] + sorted(
    p.name[len("trace_"):-len(".json.gz")]
    for p in (BENCH / "fixtures").glob("trace_*.json.gz"))


def _fixture(which):
    if which == "synthetic":
        return _synthetic()
    return (trace.read(BENCH / "fixtures" / f"trace_{which}.json.gz"),
            json.loads((BENCH / "fixtures" / f"spans_{which}.json")
                       .read_text()))


def _raster_busy(intervals, lo, hi, step):
    grid = np.zeros((hi - lo + step - 1) // step, bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo + step - 1) // step] = True
    return grid.sum() * step


@pytest.mark.parametrize("which", TRACES)
def test_busy_union_matches_a_raster(which):
    tr, _ = _fixture(which)
    lo, hi = trace.window(tr)
    dev = trace.devices(tr)[0]
    iv = trace.op_intervals(tr, dev)
    busy = trace.busy_ns(iv, lo, hi)
    assert 0 < busy < hi - lo
    # a 1 us raster rounds each interval out by at most 1 us a side
    ras = _raster_busy(iv, lo, hi, 1000)
    assert busy <= ras <= busy + 2000 * len(trace.union(
        trace.clip(iv, lo, hi)))
    b, w = trace.busy_share(tr, lo, hi)
    assert abs(b - busy / 1e9) < 1e-12 and w == (hi - lo) / 1e9
    idle = core.reader("idle_share.calibrate")(
        SimpleNamespace(trace=tr, trace_window=(lo, hi)))
    assert idle == pytest.approx(100 * (1 - b / w))


@pytest.mark.parametrize("which", TRACES)
def test_per_op_sums_and_breakdown(which):
    tr, _ = _fixture(which)
    lo, hi = trace.window(tr)
    ops = trace.op_seconds(tr, lo, hi)
    total = sum(min(s + d, hi) - max(s, lo)
                for dev in tr["ops"] for _, s, d in tr["ops"][dev]
                if min(s + d, hi) > max(s, lo)) / 1e9
    assert abs(sum(ops.values()) - total) < 1e-9
    bd = trace.breakdown(tr, lo, hi)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] == max(ops.values())
    idle = sum(trace.idle_gaps(tr, lo, hi).values())
    busy, win = trace.busy_share(tr, lo, hi)
    assert abs(idle + busy - win) < 1e-6


@pytest.mark.parametrize("which", TRACES)
def test_roofline_share_from_the_fixture(which):
    tr, spans = _fixture(which)
    ctx = SimpleNamespace(trace=tr, trace_window=trace.window(tr),
                          spans=spans, kind="TPU v5 lite")
    share = core.reader("kernels.battery_roofline")(ctx)
    assert share is not None and 0 < share <= 100


def test_synthetic_trace_by_hand():
    """The synthetic trace's numbers worked out by hand."""
    tr, spans = _synthetic()
    lo, hi = trace.window(tr)
    busy, win = trace.busy_share(tr, lo, hi)
    assert busy == pytest.approx(11 * 0.75 * (40e-6 + 25e-6))
    assert win == pytest.approx(0.02)
    ctx = SimpleNamespace(trace=tr, trace_window=(lo, hi), spans=spans,
                          kind="TPU v5 lite")
    # matmul n=512: 3 * 512^2 * 4 bytes at 819 GB/s beat 2 * 512^3 flops
    # at 197 TFLOP/s; the stream reads two arrays and writes one
    least = 3 * 512 ** 2 * 4 / 819e9 + 3 * 1048576 * 4 / 819e9
    want = 100 * least / (40e-6 + 25e-6)
    got = core.reader("kernels.battery_roofline")(ctx)
    assert got == pytest.approx(want)


def test_pausewatch_names_the_thread_that_holds_the_gil(tmp_path):
    """A stop of Python in the process, planted by a regular expression
    that backtracks in C while it holds the GIL: the watcher process sees
    the main thread use CPU for it, and did not stop itself."""
    import math
    import re
    import time

    from bench import pausewatch

    # each further "a" doubles the backtracking: size the call to ~0.8 s
    t = time.monotonic()
    re.match(r"(a+)+$", "a" * 18 + "b")
    n = 18 + max(0, round(math.log2(0.8 / max(time.monotonic() - t, 1e-6))))

    def hold():
        time.sleep(0.3)
        t = time.monotonic()
        re.match(r"(a+)+$", "a" * n + "b")
        time.sleep(0.3)
        return time.monotonic() - t

    held, rep = pausewatch.watched(hold, tmp_path)
    assert held > 0.4, "the planted call was too short to show"
    stop = max(rep["stops"], key=lambda s: s["stop_s"])
    assert stop["stop_s"] > 0.3
    assert stop["threads"][0][0] == "MainThread"
    assert stop["process_cpu_s"] > 0.5 * stop["stop_s"]
    assert stop["watcher_gap_s"] < 0.5 * stop["stop_s"]
