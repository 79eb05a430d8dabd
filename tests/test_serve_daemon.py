"""The prediction-serving daemon: coalescing, thread safety, multi-tenant
LRU, and the HTTP surface.

Serving is the steady state the whole pipeline exists for, and its three
guarantees are asserted here through the same observability probes the
CLI smoke uses:

* **zero timings** — prediction never executes a kernel, no matter how
  many threads hammer the daemon (``session.timer.calls == 0``);
* **coalescing** — K concurrent requests collapse into ONE compiled
  ``batched_breakdown`` evaluation (``session.eval_calls``) and at most
  one count lookup per unique kernel;
* **consistency under races** — the count engine's counters balance
  (hits + misses == lookups), a cold kernel raced by N threads is traced
  exactly once, and the persisted count store written under contention
  is byte-identical to one written serially.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro import spans
from repro.api import PerfSession, Prediction, PredictionError
from repro.core.calibrate import FitResult
from repro.core.countengine import CountEngine
from repro.profiles import DeviceFingerprint, MachineProfile, ModelFit
from repro.serving import (
    BatcherClosed,
    CoalescingBatcher,
    PredictionDaemon,
    SessionPool,
)
from repro.studies.zoo import OVL_FLOP_MEM

N_UNIQUE = 8


def _profile() -> MachineProfile:
    model = OVL_FLOP_MEM.model()
    fit = FitResult(params={"p_madd": 5e-11, "p_mem": 4e-10,
                            "p_launch": 3e-6, "p_edge": 40.0},
                    residual_norm=0.0, iterations=1, converged=True)
    return MachineProfile(
        fingerprint=DeviceFingerprint(platform="synth",
                                      device_kind="serve-test",
                                      n_devices=1),
        fits={OVL_FLOP_MEM.name: ModelFit.from_fit(model, fit)},
        trials=3)


def _targets(n: int = N_UNIQUE):
    """n unique in-scope (fn, args) predict items (adds + contiguous
    memory — fully inside the ovl_flop_mem model's scope)."""
    out = {}
    for i in range(n):
        size = 32 * (i + 1)
        out[f"t{i}"] = ((lambda x: x + 1.0),
                        (jnp.ones((size,), jnp.float32),))
    return out


def _session(**kw) -> PerfSession:
    return PerfSession.open(_profile(), **kw)


# ---------------------------------------------------------------------------
# CountEngine under contention
# ---------------------------------------------------------------------------


def test_cold_race_traces_each_kernel_exactly_once():
    engine = CountEngine()
    targets = list(_targets().values())
    n_threads = 16
    barrier = threading.Barrier(n_threads)

    def hammer(tid: int):
        barrier.wait()      # maximal contention on the cold path
        for i in range(len(targets) * 4):
            fn, args = targets[(tid + i) % len(targets)]
            c = engine.counts_of_callable(fn, args)
            assert c["f_op_float32_add"] == args[0].shape[0]

    with ThreadPoolExecutor(max_workers=n_threads) as pool:
        for f in [pool.submit(hammer, t) for t in range(n_threads)]:
            f.result(timeout=60)

    stats = engine.stats()
    # two threads racing one cold kernel perform exactly ONE trace
    assert stats["trace_count"] == N_UNIQUE
    assert stats["misses"] == N_UNIQUE
    lookups = n_threads * len(targets) * 4
    assert stats["hits"] + stats["misses"] == lookups


def _store_bytes(store: Path) -> dict:
    return {p.relative_to(store).as_posix(): p.read_bytes()
            for p in sorted(store.rglob("*")) if p.is_file()}


def test_contended_store_is_byte_identical_to_serial(tmp_path):
    targets = list(_targets().values())

    serial = CountEngine(store=tmp_path / "serial")
    for fn, args in targets:
        serial.counts_of_callable(fn, args)

    racy = CountEngine(store=tmp_path / "racy")
    with ThreadPoolExecutor(max_workers=16) as pool:
        futs = [pool.submit(racy.counts_of_callable, fn, args)
                for _ in range(8) for fn, args in targets]
        for f in futs:
            f.result(timeout=60)

    assert _store_bytes(tmp_path / "racy") \
        == _store_bytes(tmp_path / "serial")

    # a THIRD engine reading the racy store serves all counts traceless
    warm = CountEngine(store=tmp_path / "racy")
    for fn, args in targets:
        warm.counts_of_callable(fn, args)
    assert warm.trace_count == 0


def test_threaded_predict_zero_traces_and_timings_after_warmup(tmp_path):
    session = _session(engine=CountEngine(store=tmp_path / "store"))
    targets = list(_targets().values())
    session.predict_batch(targets)                      # warmup
    traces0 = session.engine.trace_count

    def burst(tid: int):
        fn, args = targets[tid % len(targets)]
        return session.predict(fn, *args)

    with ThreadPoolExecutor(max_workers=12) as pool:
        preds = [f.result(timeout=60)
                 for f in [pool.submit(burst, t) for t in range(24)]]

    assert all(isinstance(p, Prediction) and p.seconds > 0 for p in preds)
    assert session.engine.trace_count == traces0        # all warm
    assert session.timer.calls == 0
    stats = session.engine.stats()
    assert stats["hits"] + stats["misses"] \
        == len(targets) + 24                            # balanced ledger


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------


def test_concurrent_requests_coalesce_into_one_compiled_eval():
    session = _session()
    batcher = CoalescingBatcher(session, max_wait_s=0.001)
    try:
        batcher.hold()
        futs = [batcher.submit(item, name=name)
                for name, item in _targets().items()
                for _ in range(4)]                      # 32 requests
        assert batcher.pending_count() == 32
        batcher.release()
        preds = [f.result(timeout=60) for f in futs]
        assert all(p.seconds > 0 for p in preds)
        # ONE drained batch → ONE batched_breakdown dispatch, and dedup
        # kept count lookups at one per unique kernel
        assert session.eval_calls == 1
        eng = session.engine
        assert eng.hits + eng.misses == N_UNIQUE
        assert batcher.stats()["batches"] == 1
        assert batcher.stats()["max_batch_size"] == 32
    finally:
        batcher.close()


def test_batcher_maps_per_item_errors_to_the_right_caller():
    session = _session()
    batcher = CoalescingBatcher(session, max_wait_s=0.001)
    try:
        batcher.hold()
        good = batcher.submit((lambda x: x + 1.0,
                               (jnp.ones((64,), jnp.float32),)),
                              name="good", strict=True)
        bad = batcher.submit((lambda x: jnp.exp(x),
                              (jnp.ones((64,), jnp.float32),)),
                             name="bad", strict=True)
        batcher.release()
        # the in-scope batch-mate is unaffected...
        assert good.result(timeout=60).seconds > 0
        # ...while the out-of-scope item gets its OWN typed error
        with pytest.raises(PredictionError) as exc:
            bad.result(timeout=60)
        (v,) = exc.value.violations
        assert v["kernel"] == "bad"
        assert "f_op_float32_transc" in v["features"]
        # and the mixed batch still cost one compiled evaluation
        assert session.eval_calls == 1
    finally:
        batcher.close()


def test_closed_batcher_rejects_submits_but_drains_queue():
    session = _session()
    batcher = CoalescingBatcher(session, max_wait_s=0.001)
    batcher.hold()
    fut = batcher.submit((lambda x: x + 1.0,
                          (jnp.ones((32,), jnp.float32),)))
    batcher.close()                     # queued work drains before exit
    assert fut.result(timeout=60).seconds > 0
    with pytest.raises(BatcherClosed):
        batcher.submit((lambda x: x + 1.0,
                        (jnp.ones((32,), jnp.float32),)))


def test_strict_batch_collects_every_violation():
    session = _session()
    with pytest.raises(PredictionError) as exc:
        session.predict_batch(
            [(lambda x: x + 1.0, (jnp.ones((32,), jnp.float32),)),
             (lambda x: jnp.exp(x), (jnp.ones((32,), jnp.float32),)),
             (lambda x: jnp.sin(x), (jnp.ones((64,), jnp.float32),))],
            names=["ok", "bad_exp", "bad_sin"], strict=True)
    vs = exc.value.violations
    # BOTH offenders reported in one error, mapped to their indices
    assert [(v["index"], v["kernel"]) for v in vs] \
        == [(1, "bad_exp"), (2, "bad_sin")]
    assert all("f_op_float32_transc" in v["features"] for v in vs)
    assert "bad_exp" in str(exc.value) and "bad_sin" in str(exc.value)


# ---------------------------------------------------------------------------
# the LRU session pool
# ---------------------------------------------------------------------------


def test_session_pool_lru_eviction_and_reopen(tmp_path):
    opened = []

    def factory(path, *, cache=None):
        opened.append(path)
        return _session()

    pool = SessionPool(max_open=2, session_factory=factory)
    try:
        s1, b1 = pool.get("p1")
        s2, _ = pool.get("p2")
        assert pool.get("p1") == (s1, b1)               # LRU refresh: hit
        pool.get("p3")                                  # evicts p2 (LRU)
        assert pool.stats() == {"open": 2, "opens": 3, "hits": 1,
                                "evictions": 1}
        s2b, _ = pool.get("p2")                         # reopen evicts p1
        assert s2b is not s2
        assert opened == ["p1", "p2", "p3", "p2"]
        # the evicted entry's batcher was closed on the way out
        with pytest.raises(BatcherClosed):
            b1.submit((lambda x: x + 1.0,
                       (jnp.ones((16,), jnp.float32),)))
    finally:
        pool.close()


def test_session_pool_serves_through_fresh_batcher_after_eviction():
    def factory(path, *, cache=None):
        return _session()

    pool = SessionPool(max_open=1, session_factory=factory,
                       max_wait_s=0.001)
    try:
        _, b1 = pool.get("p1")
        _, b2 = pool.get("p2")                          # evicts + closes b1
        with pytest.raises(BatcherClosed):
            b1.submit((lambda x: x + 1.0,
                       (jnp.ones((16,), jnp.float32),)))
        pred = b2.predict((lambda x: x + 1.0,
                           (jnp.ones((16,), jnp.float32),)),
                          timeout=60)
        assert pred.seconds > 0
        assert pool.stats()["evictions"] == 1
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# the HTTP daemon
# ---------------------------------------------------------------------------


@pytest.fixture
def daemon():
    d = PredictionDaemon(_session(), port=0, targets=_targets(4),
                         max_wait_s=0.001).start()
    yield d
    d.close()


def _post(url: str, body: dict, timeout: float = 60.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_daemon_serves_concurrent_burst_with_one_eval(daemon):
    burst = 16
    daemon.batcher.hold()
    with ThreadPoolExecutor(max_workers=burst) as pool:
        futs = [pool.submit(_post, f"{daemon.url}/predict",
                            {"kernel": f"t{i % 4}"})
                for i in range(burst)]
        deadline = time.monotonic() + 30.0
        while daemon.batcher.pending_count() < burst:
            assert time.monotonic() < deadline, \
                f"only {daemon.batcher.pending_count()}/{burst} parked"
            time.sleep(0.005)
        daemon.batcher.release()
        replies = [f.result(timeout=60) for f in futs]

    assert all(status == 200 for status, _ in replies)
    assert all(body["seconds"] > 0 and body["model"] == "ovl_flop_mem"
               for _, body in replies)
    stats = daemon.stats()
    assert stats["timings"] == 0
    assert stats["eval_calls"] == 1
    assert stats["count_lookups"] <= 4
    assert stats["batcher"]["max_batch_size"] == burst


def test_daemon_http_error_codes(daemon):
    status, body = _post(f"{daemon.url}/predict", {"kernel": "nope"})
    assert status == 404 and "t0" in body["known"]
    status, body = _post(f"{daemon.url}/predict", {})
    assert status == 400
    # strict + out-of-scope → 422 carrying the violation record
    daemon.targets["exp"] = ((lambda x: jnp.exp(x)),
                             (jnp.ones((64,), jnp.float32),))
    status, body = _post(f"{daemon.url}/predict",
                         {"kernel": "exp", "strict": True})
    assert status == 422
    (v,) = body["violations"]
    assert v["features"] == ["f_op_float32_transc"]


def test_daemon_stats_carry_spans(daemon):
    """``GET /stats`` serves the span totals, and the drained batch is a
    ``serve.batch`` root whose pricing spans hang under it."""
    t0 = time.perf_counter_ns()
    status, _ = _post(f"{daemon.url}/predict", {"kernel": "t1"})
    assert status == 200
    # the answer is sent from inside the batch's span: wait for it to end
    deadline = time.monotonic() + 30
    while not [s for s in spans.between(t0, time.perf_counter_ns())
               if s.name == "serve.batch"]:
        assert time.monotonic() < deadline, "serve.batch never ended"
        time.sleep(0.005)
    with urllib.request.urlopen(f"{daemon.url}/stats", timeout=30) as r:
        stats = json.loads(r.read())
    for name in ("serve.batch", "price.batch", "price.eval"):
        got = stats["spans"][name]
        assert got["count"] >= 1 and got["seconds"] > 0
    served = stats["spans"]["serve.batch"]
    assert served["size"] >= 1 and served["queue_wait_s"] >= 0
    done = spans.between(t0, time.perf_counter_ns())
    (batch,) = [s for s in done if s.name == "serve.batch"]
    assert batch.parent == 0 and batch.attrs["size"] == 1
    assert batch.attrs["queue_wait_s"] >= 0
    (price,) = [s for s in done if s.name == "price.batch"]
    assert price.parent == batch.id and price.root == batch.id
    assert price.attrs["rows"] == 1


def test_daemon_stats_and_shutdown_routes(daemon):
    with urllib.request.urlopen(f"{daemon.url}/healthz", timeout=30) as r:
        assert json.loads(r.read()) == {"ok": True}
    _post(f"{daemon.url}/predict", {"kernel": "t0"})
    with urllib.request.urlopen(f"{daemon.url}/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert stats["timings"] == 0 and stats["batcher"]["requests"] == 1
    status, body = _post(f"{daemon.url}/shutdown", {})
    assert status == 200 and body == {"ok": True}
    # the listener actually stopped
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        try:
            urllib.request.urlopen(f"{daemon.url}/healthz", timeout=1)
            time.sleep(0.02)
        except (urllib.error.URLError, ConnectionError, OSError):
            break
    else:
        pytest.fail("daemon kept answering after /shutdown")
