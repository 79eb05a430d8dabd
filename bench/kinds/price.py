"""An open loop of price requests into ``CoalescingBatcher.submit``.

Set-up calibrates a profile on the chip (``repro.calibrate --zoo``),
opens a ``PerfSession`` on it, prices every catalog item once, builds
the families a service that prices the catalog's generators holds
(:func:`_families`), and warms the batched evaluator at every row count
up to the batcher's ``max_batch``, so that the evaluator compiles
nothing in the window.  A never-seen kernel's first sight (a family of
another generator with its probe traces, an exact trace with its
arrays, a Pallas subject's jaxpr) is the window's own work, as it is a
running service's.  The window sends
each request when it is due (``bench.traffic``), from this one thread;
the batcher's drainer answers them.  After the window every request gets
``wait_after_s`` (a minute) to be answered.

End-to-end: ``price_p95_ms``, the 95th percentile of every request's
latency from when it was due; one unanswered or failed counts as the
whole wait.

``correct``: a sample of the answered requests drawn from the seed, with
every Pallas shape among them once (the longest requests), each
request's counts against the plain
counter, its price against the rung evaluated in float64 from the
profile's parameters and those counts, and that each answer went to the
request that asked.
"""
from __future__ import annotations

import shutil
import threading
import time
from typing import Any, Dict, List

import numpy as np

from bench import reference as R
from bench import traffic as T
from bench.core import percentile


def run(h) -> Dict[str, Any]:
    from repro.api import PerfSession
    from repro.core.counting import FeatureCounts
    from repro.profiles import cli
    from repro.serving.coalesce import CoalescingBatcher

    tr = h.traffic

    # ---- set-up
    d = h.scratch / "profile"
    shutil.rmtree(d, ignore_errors=True)
    cal = tr["calibrate"]
    rc = cli.main(["--zoo", "--tags", *cal["tags"], "--trials",
                   str(cal["trials"]), "--cache-dir", str(d / "measurements"),
                   "--out", str(d / "profile.json")])
    if rc != 0:
        raise RuntimeError(f"repro.calibrate exited {rc}")
    session = PerfSession.open(str(d / "profile.json"))
    cat = T.catalog(h.config, tr)
    new = T.novel(h.config, tr, cat)
    sent = {id(i): i.request() for items in (cat, new)
            for v in items.values() for i in v}
    flat = [i for v in cat.values() for i in v]
    session.predict_batch([sent[id(i)] for i in flat],
                          names=[i.label for i in flat])
    _families(session, cat["battery"])
    max_batch = int(tr["max_batch"])
    engine = session.predict_engine
    for n in range(1, max_batch + 1):
        engine.predict_rows([FeatureCounts({R.LAUNCH: 1.0})] * n,
                            [f"warm{j}" for j in range(n)])
    batcher = CoalescingBatcher(session, max_batch=max_batch,
                                max_wait_s=float(tr["max_wait_s"]))
    # spans around the drainer's calls into each layer, so that the
    # trace's idle gaps are put under what the host was doing
    h.spans.wrap(batcher, "_execute", "bench.batch")
    h.spans.wrap(session.engine, "_trace", "bench.count_trace")
    h.spans.wrap(engine, "_predict", "bench.evaluate")
    try:
        out = _serve(h, tr, session, batcher, sent, cat, new,
                     float(tr["rate_per_s"]))
    finally:
        batcher.close()
    h.finish()
    out["ctx"] = h.ctx(**out["ctx"])
    t_ref = time.perf_counter()
    out["checks"] = _checks(h, out.pop("plan"), out.pop("preds"), session,
                            out["failed"], tr)
    print(f"[bench] reference took {time.perf_counter() - t_ref:.1f} s",
          flush=True)
    return out


def _families(session, catalog) -> None:
    """The count store of a service that has priced its catalog's
    generators a while: the symbolic family of every member of each
    generator the catalog draws on, taken from the generator's own
    argument space, not from the requests to come."""
    import json

    from repro.core.uipick import ALL_GENERATORS

    names = {json.loads(i.kernel.family.key)["gen"] for i in catalog
             if i.kernel.family is not None}
    families = {k.family.key: k.family for g in ALL_GENERATORS
                if g.name in names for k in g.variants({})
                if k.family is not None}
    for fam in families.values():
        session.engine.symbolic(fam)


def _serve(h, tr, session, batcher, sent, cat, new, rate):
    """The window at ``rate``: send every request when due, then give each
    ``wait_after_s`` to be answered.  Backlog at the close and the time to
    drain it say whether the rate is above what the service sustains."""
    engine = session.predict_engine
    plan = T.schedule(tr, cat, new, h.seconds, rate, h.seed)
    n = len(plan)
    done = [None] * n                   # perf_counter when answered
    results: List[Any] = [None] * n
    lock = threading.Lock()

    def answered(i):
        def cb(fut):
            t = time.perf_counter()
            with lock:
                done[i] = t
                results[i] = fut
        return cb

    before = (session.engine.stats(), engine.trace_count,
              engine.eval_calls, batcher.stats())
    late = []
    with h.window():
        t0 = time.perf_counter()
        for i, (due, item) in enumerate(plan):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late.append(time.perf_counter() - (t0 + due))
            fut = batcher.submit(sent[id(item)], name=f"r{i}")
            fut.add_done_callback(answered(i))
        while time.perf_counter() < t0 + h.seconds:
            time.sleep(0.001)
    close = time.perf_counter()
    with lock:
        backlog = sum(t is None for t in done)
    while time.perf_counter() < close + float(tr["wait_after_s"]):
        with lock:
            if all(t is not None for t in done):
                break
        time.sleep(0.01)
    end = time.perf_counter()
    after = (session.engine.stats(), engine.trace_count, engine.eval_calls,
             batcher.stats())

    lat, failed, preds = [], 0, {}
    for i, (due, item) in enumerate(plan):
        fut = results[i]
        if fut is not None and fut.exception() is None:
            preds[i] = fut.result()
            lat.append(done[i] - (t0 + due))
        else:
            failed += 1
            lat.append(end - (t0 + due))
            print(f"[bench] request r{i} ({item.label}) unanswered: "
                  f"{fut.exception() if fut is not None else 'no answer'!r}",
                  flush=True)
    half = n // 2
    print(f"[bench] rate {rate}/s: {n} requests, backlog at close "
          f"{backlog}, drained {end - close:.2f} s after; p95 first half "
          f"{1e3 * percentile(lat[:half] or lat, 95):.1f} ms, second half "
          f"{1e3 * percentile(lat[half:], 95):.1f} ms, all "
          f"{1e3 * percentile(lat, 95):.1f} ms; generator late p95 "
          f"{1e3 * percentile(late, 95):.1f} ms", flush=True)
    (c0, tc0, ev0, b0), (c1, tc1, ev1, b1) = before, after
    print(f"[bench] {n} requests at {rate}/s: {failed} unanswered; "
          f"count {c0} -> {c1}; evaluator traces {tc0} -> {tc1}; "
          f"batches {b0} -> {b1}", flush=True)
    return {"attempted": n, "failed": failed,
            "metrics": {"price_p95_ms": 1e3 * percentile(lat, 95)},
            "ctx": dict(counts=(c0, c1), eval_traces=(tc0, tc1),
                        evals=(ev0, ev1), batches=(b0, b1), late_s=late),
            "plan": plan, "preds": preds}


def _checks(h, plan, preds, session, failed, tr) -> Dict[str, Dict]:
    import jax.numpy as jnp

    rng = h.rng(2)
    answered = sorted(preds)
    # the longest requests, the Pallas subjects, once per shape; the rest
    # drawn from the seed
    first: Dict[int, int] = {}
    for i in answered:
        if plan[i][1].kind == "pallas":
            first.setdefault(id(plan[i][1]), i)
    longest = sorted(first.values())
    rest = sorted(set(answered) - set(longest))
    k = max(0, min(len(rest), int(tr["sample"]) - len(longest)))
    sample = longest + [rest[j] for j in rng.permutation(len(rest))[:k]]
    ref_counts: Dict[int, Dict[str, float]] = {}
    fits = session.profile.fits
    count_gap = price_gap = 0.0
    misrouted = 0
    for i in sample:
        item = plan[i][1]
        p = preds[i]
        if id(item) not in ref_counts:
            fn, args = item.fn_args()
            ref_counts[id(item)] = R.count(fn, args)
        c = ref_counts[id(item)]
        if p.kernel != f"r{i}":
            misrouted += 1
        got = np.array([p.features.get(f, 0.0) for f in R.FEATURES])
        want = np.array([c[f] for f in R.FEATURES])
        count_gap = max(count_gap, float(np.max(
            np.abs(got - want) / np.maximum(np.abs(want), 1.0))))
        params = fits[p.model].params
        ref = float(R.price(p.model, params, c))
        val = p.seconds
        if h.control:
            val = float(R.price(p.model, params, c, xp=jnp,
                                dtype=jnp.bfloat16))
        price_gap = max(price_gap, abs(val - ref) / ref)
    lim = tr["limits"]
    print(f"[bench] reference checked {len(sample)} of {len(preds)} "
          f"answers ({len(longest)} Pallas shapes)", flush=True)
    return {
        "unanswered": {"value": failed, "limit": 0},
        "misrouted": {"value": misrouted, "limit": 0},
        "count_gap": {"value": count_gap, "limit": lim["count_gap"]},
        "price_gap": {"value": price_gap, "limit": lim["price_gap"]},
    }
