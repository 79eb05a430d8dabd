"""The span recorder (``repro.spans``): nesting, the ring's bound, compile
attrs on the innermost span, self time, and the spans of one calibration
through the CLI."""
from __future__ import annotations

import collections
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.profiles import cli
from repro.spans import Recorder, Span, self_ns


def test_nesting_parent_root_and_threads():
    rec = Recorder()
    inside = threading.Barrier(2, timeout=30)

    def work(tag):
        with rec.span(f"{tag}.outer") as outer:
            with rec.span(f"{tag}.mid") as mid:
                inside.wait()           # both threads are nested here
                with rec.span(f"{tag}.inner", k=1) as inner:
                    pass
        got[tag] = (outer, mid, inner)

    got = {}
    t = threading.Thread(target=work, args=("b",))
    t.start()
    work("a")
    t.join(timeout=30)
    assert not t.is_alive()
    by_name = {s.name: s for s in rec.between(0, time.perf_counter_ns())}
    assert len(by_name) == 6
    for tag in "ab":
        outer, mid, inner = (by_name[f"{tag}.{n}"]
                             for n in ("outer", "mid", "inner"))
        assert outer.parent == 0 and outer.root == outer.id
        assert mid.parent == outer.id and mid.root == outer.id
        assert inner.parent == mid.id and inner.root == outer.id
        assert inner.attrs == {"k": 1}
        assert outer.t0_ns <= mid.t0_ns <= inner.t0_ns \
            <= inner.t1_ns <= mid.t1_ns <= outer.t1_ns
        assert len({outer.thread, mid.thread, inner.thread}) == 1
    assert by_name["a.outer"].thread != by_name["b.outer"].thread
    assert by_name["a.outer"].root != by_name["b.outer"].root
    # finished spans come back by start
    starts = [s.t0_ns for s in rec.between(0, time.perf_counter_ns())]
    assert starts == sorted(starts)


def test_ring_is_bounded_and_totals_are_not():
    rec = Recorder(maxlen=4)
    for i in range(10):
        with rec.span("step", i=i):
            pass
    kept = rec.between(0, time.perf_counter_ns())
    assert [s.attrs["i"] for s in kept] == [6, 7, 8, 9]
    tot = rec.totals()["step"]
    assert tot["count"] == 10
    assert tot["seconds"] >= sum(s.seconds for s in kept)


def test_totals_sum_numeric_attrs():
    rec = Recorder()
    for i, wait in enumerate((0.25, 0.5, 1.0)):
        with rec.span("serve.batch", size=i + 1, queue_wait_s=wait,
                      model="m", converged=i > 0):
            pass
    with rec.span("other") as other:
        other.attrs["compiles"] = 2
    tot = rec.totals()
    assert tot["serve.batch"]["count"] == 3
    assert tot["serve.batch"]["size"] == 6
    assert tot["serve.batch"]["queue_wait_s"] == 1.75
    assert tot["serve.batch"]["converged"] == 2     # spans that set it
    assert "model" not in tot["serve.batch"]
    assert tot["other"]["compiles"] == 2 and "size" not in tot["other"]


def test_compile_events_without_an_open_span_are_unowned():
    rec = Recorder()
    rec.on_duration("/jax/core/compile/backend_compile_duration", 0.5)
    rec.on_event("/jax/compilation_cache/cache_misses")
    with rec.span("owned") as owned:
        rec.on_duration("/jax/core/compile/backend_compile_duration", 0.25)
        rec.on_event("/jax/compilation_cache/cache_hits")
    assert rec.unowned() == {"compiles": 1, "cache_hits": 0,
                             "cache_misses": 1, "compile_s": 0.5}
    assert owned.attrs == {"compiles": 1, "compile_s": 0.25,
                           "cache_hits": 1}
    # the process's recorder hears JAX itself
    before = spans.unowned()
    jax.block_until_ready(jax.jit(lambda v: v - 7.0)(jnp.arange(5.0)))
    after = spans.unowned()
    assert after["compiles"] >= before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]


def test_add_counts_into_the_innermost_span_only():
    rec = Recorder()
    rec.add("reused")                    # no span open: nothing
    with rec.span("outer", reused=0) as outer:
        with rec.span("inner") as inner:
            rec.add("reused")
            rec.add("reused", 2)
        rec.add("reused")
    assert inner.attrs == {"reused": 3} and outer.attrs == {"reused": 1}
    assert rec.unowned() == dict.fromkeys(
        ("compiles", "cache_hits", "cache_misses", "compile_s"), 0)
    assert rec.totals()["outer"]["reused"] == 1


def test_between_is_half_open_on_start():
    rec = Recorder()
    with rec.span("x") as x:
        pass
    assert rec.between(x.t0_ns, x.t0_ns + 1)[0].name == "x"
    assert rec.between(0, x.t0_ns) == []


def test_span_that_raises_is_kept_with_its_error():
    rec = Recorder()
    with pytest.raises(KeyError):
        with rec.span("lookup"):
            raise KeyError("k")
    (s,) = rec.between(0, time.perf_counter_ns())
    assert s.attrs == {"error": "KeyError"} and rec.innermost() is None


def test_compile_attrs_land_on_the_innermost_span():
    x = jnp.arange(11.0)
    t0 = time.perf_counter_ns()
    with spans.span("test.outer") as outer:
        with spans.span("test.compile") as inner:
            jax.block_until_ready(jax.jit(lambda v: v * 3.0 + 1.0)(x))
        with spans.span("test.quiet") as quiet:
            pass
    assert inner.attrs["compiles"] >= 1 and inner.attrs["compile_s"] > 0
    assert inner.attrs.get("cache_hits", 0) <= inner.attrs["compiles"]
    assert "compiles" not in outer.attrs and "compiles" not in quiet.attrs
    (rec,) = [s for s in spans.between(t0, time.perf_counter_ns())
              if s.name == "test.compile"]
    assert rec.attrs["compiles"] == inner.attrs["compiles"]


def _span(id_, parent, t0, t1, name="s"):
    return Span(id_, parent, 1, name, t0, t1, 0, {})


def test_self_time_with_overlapping_children():
    parent = _span(1, 0, 0, 1000)
    got = [parent,
           _span(2, 1, 100, 400),
           _span(3, 1, 300, 500),      # overlaps 2 (another thread's)
           _span(4, 1, 900, 1200),     # runs past the parent: clipped
           _span(5, 2, 150, 200),      # a grandchild: inside 2 already
           _span(6, 0, 600, 700)]      # not a child
    # children cover [100, 500) and [900, 1000)
    assert self_ns(parent, got) == 1000 - 400 - 100
    assert self_ns(_span(7, 0, 0, 50), got) == 50
    # a child that holds another: counted once
    assert self_ns(parent, [_span(8, 1, 0, 1000), _span(9, 1, 10, 20)]) == 0


@pytest.mark.parametrize("timer", ["synthetic", "host"])
def test_zoo_calibration_spans(tmp_path, timer):
    """One ``calibrate.profile`` root per calibration, one
    ``measure.kernel`` per battery row, and nothing inside a timed pass.
    ``host`` times the battery on this machine (the default timer)."""
    argv = ["--zoo", "--smoke", "--trials", "2",
            "--out", str(tmp_path / "p.json"),
            "--cache-dir", str(tmp_path / "cache")]
    if timer == "synthetic":
        argv += ["--synthetic", "apex"]
    t0 = time.perf_counter_ns()
    assert cli.main(argv) == 0
    got = spans.between(t0, time.perf_counter_ns())
    (root,) = [s for s in got if s.name == "calibrate.profile"]
    assert root.parent == 0 and root.attrs["trials"] == 2
    mine = [s for s in got if s.root == root.id]
    assert len(mine) == len(got)            # one calibration, one tree
    names = collections.Counter(s.name for s in mine)
    rows = root.attrs["kernels"]
    assert names["measure.kernel"] == rows > 2
    for once in ("calibrate.battery", "measure.gather", "count.batch",
                 "solve.identify", "solve.fit", "calibrate.save"):
        assert names[once] == 1, once
    assert names["solve.rung"] == 3
    ids = {s.id: s for s in mine}
    for s in mine:
        if s.name in ("measure.kernel", "count.batch"):
            assert ids[s.parent].name == "measure.gather"
        if s.name == "solve.rung":
            assert ids[s.parent].name == "solve.fit"
            assert set(s.attrs) >= {"model", "iterations", "converged"}
    (lookup,) = [s for s in mine if s.name == "measure.cache"
                 and "hits" in s.attrs]
    assert (lookup.attrs["hits"], lookup.attrs["misses"]) == (0, rows)
    (batch,) = [s for s in mine if s.name == "count.batch"]
    assert batch.attrs["rows"] == rows and batch.attrs["traces"] > 0
    assert batch.attrs["families_built"] > 0
    traces = [s for s in mine if s.name == "count.trace"]
    assert len(traces) == batch.attrs["traces"]
    assert all(s.attrs["lock_wait_s"] >= 0 for s in traces)
    # one acquire holds the whole batch: its wait rides the first trace
    assert [s.attrs["lock_wait_s"] for s in traces[1:]] \
        == [0.0] * (len(traces) - 1)
    timed = [s for s in mine if s.name == "measure.time"]
    if timer == "host":
        for step in ("measure.args", "measure.load", "measure.time"):
            assert names[step] == rows, step
            assert all(ids[s.parent].name == "measure.kernel"
                       for s in mine if s.name == step)
    else:
        assert not timed
    assert not [s for s in mine if s.parent in {t.id for t in timed}]
