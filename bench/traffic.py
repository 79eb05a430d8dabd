"""The one generator of open-loop price traffic, driven by a traffic file.

The catalog is the configuration's subjects at the file's sizes plus the
measurement-kernel families its UIPiCK tag lists select; ``novel`` lists
shapes outside the catalog.  The requests of a run are a fixed multiset
drawn once from the file's ``fixed_seed``: each kind's share of the mix,
Zipf popularity within a kind, the novel share spread over the kinds, and
Gamma inter-arrival gaps with the file's mean rate and coefficient of
variation.  The run's ``--seed`` only reorders requests among nearby
arrivals, so every seed offers the same work at the same times in
another order.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import subjects as S


@dataclass
class Item:
    """One priceable thing: a subject at sizes, or a measurement kernel."""

    kind: str                 # pallas | battery
    label: str
    spec: Dict[str, Any]      # subject spec, or {} for a measurement kernel
    sizes: Dict[str, int]
    kernel: Any = None        # the MeasurementKernel of a battery item

    def request(self):
        """What the request sends: a measurement kernel, or a callable
        with abstract arguments."""
        if self.kernel is not None:
            return self.kernel
        return S.build(self.spec, **self.sizes)

    def fn_args(self):
        """The callable and abstract arguments the reference counts."""
        import jax

        if self.kernel is not None:
            return self.kernel.fn, jax.eval_shape(self.kernel.make_args)
        return S.build(self.spec, **self.sizes)


def _subject_items(config, sizes) -> Dict[str, List[Item]]:
    out: Dict[str, List[Item]] = {"pallas": []}
    grid = sizes.get("pallas", {})
    names = sorted(grid)
    for spec in config["subjects"]:
        for combo in itertools.product(*(grid[n] for n in names)):
            sz = dict(zip(names, (int(v) for v in combo)))
            label = spec["name"] + "[" + ",".join(
                f"{k}={v}" for k, v in sz.items()) + "]"
            out["pallas"].append(Item("pallas", label, spec, sz))
    return out


def _battery(tag_lists) -> List[Item]:
    from repro.core.uipick import (
        ALL_GENERATORS, KernelCollection, MatchCondition,
    )

    seen, out = set(), []
    for tags in tag_lists:
        for k in KernelCollection(ALL_GENERATORS).generate_kernels(
                tags, generator_match_cond=MatchCondition.INTERSECT):
            if k.name not in seen:
                seen.add(k.name)
                out.append(Item("battery", k.name, {}, dict(k.sizes), k))
    return out


def catalog(config, traffic) -> Dict[str, List[Item]]:
    """kind → the items set-up prices once."""
    out = _subject_items(config, traffic["sizes"])
    out["battery"] = _battery(traffic["battery_tags"])
    return out


def novel(config, traffic, known: Dict[str, List[Item]]
          ) -> Dict[str, List[Item]]:
    """kind → items outside the catalog, which set-up never prices."""
    spec = traffic["novel"]
    out = _subject_items(config, spec["sizes"])
    labels = {i.label for items in known.values() for i in items}
    out["battery"] = [i for i in _battery(spec["battery_tags"])
                      if i.label not in labels]
    return {k: [i for i in v if i.label not in labels]
            for k, v in out.items()}


def requests(traffic, cat: Dict[str, List[Item]],
             new: Dict[str, List[Item]], seconds: float, rate: float
             ) -> Tuple[List[Item], np.ndarray]:
    """The fixed multiset of requests for a window of ``seconds`` at
    ``rate``, and their gaps in seconds, before the run's ordering."""
    rng = np.random.default_rng(int(traffic["fixed_seed"]))
    n = max(1, int(round(rate * seconds)))
    mix = traffic["mix"]
    kinds = sorted(mix)
    n_novel = int(round(float(traffic["novel_share"]) * n))
    per_kind = _apportion(n - n_novel, [mix[k] for k in kinds])
    novel_kind = _apportion(n_novel, [mix[k] for k in kinds])
    out: List[Item] = []
    s = float(traffic["zipf_s"])
    for kind, m, m_new in zip(kinds, per_kind, novel_kind):
        items = list(cat[kind])
        order = rng.permutation(len(items))        # popularity ranks
        w = 1.0 / np.arange(1, len(items) + 1) ** s
        counts = _apportion(m, list(w / w.sum()))
        for rank, c in enumerate(counts):
            out += [items[order[rank]]] * c
        pool = list(new[kind])
        if m_new > len(pool):
            raise ValueError(f"{m_new} novel {kind} requests but only "
                             f"{len(pool)} novel shapes")
        out += [pool[i] for i in rng.permutation(len(pool))[:m_new]]
    cv = float(traffic["cv"])
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / (shape * rate), size=n)
    # the window offers exactly n requests in ``seconds``
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return out, gaps


def _apportion(n: int, weights: List[float]) -> List[int]:
    """Split ``n`` by ``weights`` in whole numbers (largest remainders)."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return [int(x) for x in out]


def schedule(traffic, cat, new, seconds: float, rate: float, seed: int
             ) -> List[Tuple[float, Item]]:
    """(due second, item) for every request of the run, in order.  The
    arrival times and a base order of the requests come from the file's
    ``fixed_seed``; the run's seed shuffles the requests within each run
    of ``shuffle_block`` consecutive arrivals, so that what is asked
    changes from seed to seed but when the load peaks, and where each
    rare shape falls, does not."""
    items, gaps = requests(traffic, cat, new, seconds, rate)
    base = np.random.default_rng(int(traffic["fixed_seed"]) + 1)
    items = [items[i] for i in base.permutation(len(items))]
    rng = np.random.default_rng([int(seed), 1])
    block = int(traffic["shuffle_block"])
    order: List[int] = []
    for lo in range(0, len(items), block):
        hi = min(lo + block, len(items))
        order += [lo + int(j) for j in rng.permutation(hi - lo)]
    due = np.cumsum(gaps)
    return list(zip(due.tolist(), [items[i] for i in order]))
