"""The rules ``BENCHMARK.json`` keeps, read with no chip and no JAX.

    python -m pytest bench/tests/test_manifest.py -q

One case per rule and, for the rules of a cell, per cell: a cell's files
exist, it reports an end-to-end metric besides ``setup_s`` and a
per-layer metric; every ``workloads`` list names real cells, every
per-layer metric has its reader, every ``source`` fits in 200
characters, and at most half the cells, rounded down, ask for 4 chips.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BM["workloads"]}
METRICS = BM["end_to_end"] + BM["per_layer"]


def _reports(cell: str, metrics) -> list:
    return [m["name"] for m in metrics
            if cell in m.get("workloads", [cell])]


def cell_files(cell):
    w = CELLS[cell]
    config = next(c for c in BM["configs"] if c["name"] == w["config"])
    assert config["file"] == f"bench/configs/{w['config']}.json"
    for path in (config["file"], f"bench/traffic/{w['traffic']}.json"):
        assert (ROOT / path).is_file(), path


def cell_end_to_end(cell):
    """The rule that keeps a cell from showing nothing when it worsens."""
    assert set(_reports(cell, BM["end_to_end"])) - {"setup_s"}


def cell_per_layer(cell):
    assert _reports(cell, BM["per_layer"])


def workloads_are_cells(_):
    for m in METRICS:
        assert set(m.get("workloads", [])) <= set(CELLS), m["name"]


def readers_exist(_):
    for m in BM["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), \
            m["name"]


def sources_fit(_):
    for m in METRICS + BM["configs"]:
        assert 1 <= len(m["source"]) <= 200, m["name"]


def four_chip_share(_):
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= len(BM["workloads"]) // 2


PER_CELL = [cell_files, cell_end_to_end, cell_per_layer]
GLOBAL = [workloads_are_cells, readers_exist, sources_fit, four_chip_share]
CASES = [(rule, cell) for rule in PER_CELL for cell in CELLS] \
    + [(rule, None) for rule in GLOBAL]


@pytest.mark.parametrize(
    "rule,cell", CASES,
    ids=[f"{r.__name__}-{c}" if c else r.__name__ for r, c in CASES])
def test_manifest_rule(rule, cell):
    rule(cell)
