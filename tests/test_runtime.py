"""Straggler-monitor semantics and the trainer's straggler path.

The monitor is the single-machine ancestor of the fleet health layer
(``repro.fleet.FleetHealth``): wall time vs a model-predicted expectation,
flag past ``slack ×``.  The load-bearing property regression-tested here
is window hygiene — flagged samples must stay OUT of the running-median
window, otherwise repeated stragglers inflate the expectation until they
look normal and mask themselves.

The trainer test runs the REAL ``Trainer.train`` loop (timing, monitor
wiring, metrics log) with the expensive parts stubbed: the jitted train
step is replaced by a fake that sleeps on a chosen step, and the data
pipeline by a trivial iterator — so the straggler path is exercised in
milliseconds without compiling a model.
"""
import itertools
import time

import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape, OptimizerConfig, RunConfig
from repro.runtime import StragglerMonitor, Trainer
from repro.runtime.trainer import TrainState


# ---------------------------------------------------------------------------
# StragglerMonitor
# ---------------------------------------------------------------------------


def test_predicted_expectation_mode():
    mon = StragglerMonitor(slack=2.0, predicted_step_s=0.1)
    assert mon.expectation() == 0.1         # model prediction, immediately
    assert mon.observe(1, 0.15) is None
    ev = mon.observe(2, 0.3)
    assert ev is not None
    assert ev.step == 2
    assert ev.expected_s == 0.1
    assert ev.ratio == pytest.approx(3.0)
    assert mon.events == [ev]


def test_median_fallback_needs_five_samples():
    mon = StragglerMonitor(slack=2.0)
    for i in range(4):
        assert mon.observe(i, 10.0) is None  # no expectation yet
    assert mon.expectation() is None
    mon.observe(4, 10.0)
    assert mon.expectation() == pytest.approx(10.0)
    assert mon.observe(5, 25.0) is not None


def test_median_fallback_uses_windowed_median():
    mon = StragglerMonitor(slack=2.0, window=4)
    for i, t in enumerate([1.0, 1.0, 1.0, 1.0, 1.0]):
        mon.observe(i, t)
    # window drops the early samples: median over the LAST 4
    for i, t in enumerate([0.2, 0.2, 0.2, 0.2], start=5):
        mon.observe(i, t)
    assert mon.expectation() == pytest.approx(0.2)


def test_flagged_samples_stay_out_of_the_window():
    # regression: a run of stragglers must NOT drag the expectation up —
    # if flagged samples entered the window, the 10th identical straggler
    # would look normal and the monitor would go blind
    mon = StragglerMonitor(slack=3.0)
    for i in range(5):
        mon.observe(i, 0.1)
    for i in range(5, 15):
        ev = mon.observe(i, 1.0)
        assert ev is not None, f"straggler at step {i} was masked"
        assert ev.expected_s == pytest.approx(0.1)
    assert mon.expectation() == pytest.approx(0.1)
    assert len(mon._times) == 5             # window holds clean samples only
    assert len(mon.events) == 10


def test_on_straggler_callback_fires_per_event():
    seen = []
    mon = StragglerMonitor(slack=2.0, predicted_step_s=0.1,
                           on_straggler=seen.append)
    mon.observe(1, 0.1)
    mon.observe(2, 0.5)
    mon.observe(3, 0.12)
    mon.observe(4, 0.9)
    assert [e.step for e in seen] == [2, 4]
    assert seen == mon.events


# ---------------------------------------------------------------------------
# Trainer straggler path (real loop, stubbed step + data)
# ---------------------------------------------------------------------------


def _tiny_run(tmp_path, **kw):
    cfg = get_smoke_config("yi-6b")
    shape = InputShape("tiny", seq_len=32, global_batch=8, kind="train")
    return RunConfig(
        model=cfg, shape=shape,
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=5,
                                  total_steps=100),
        microbatches=2, checkpoint_every=0,
        checkpoint_dir=str(tmp_path / "ckpt"), max_step_retries=3, **kw)


def test_trainer_flags_slow_step_against_model_prediction(
        tmp_path, monkeypatch):
    run = _tiny_run(tmp_path, straggler_slack=3.0)
    tr = Trainer(run, mesh=None, predicted_step_s=0.01)
    flagged = []
    tr.monitor.on_straggler = flagged.append

    # materialize the loss once up front: the first jnp array of the
    # process pays backend init, which would flag step 1 as a straggler
    loss = jnp.float32(1.0)

    def fake_step(params, opt_state, batch):
        # Trainer increments step AFTER the call: this executes step 3
        # when state.step == 2, i.e. on the third call
        if fake_step.calls == 2:
            time.sleep(0.08)                # 8× prediction: a straggler
        fake_step.calls += 1
        return params, opt_state, {"loss": loss}

    fake_step.calls = 0
    monkeypatch.setattr(tr, "_train_step", fake_step)
    monkeypatch.setattr("repro.runtime.trainer.make_batch_iterator",
                        lambda *a, **kw: itertools.repeat(None))

    state = tr.train(TrainState({}, {}, 0), 5, log_every=0)
    assert state.step == 5
    assert [e.step for e in flagged] == [3]
    assert flagged == tr.monitor.events
    assert flagged[0].expected_s == 0.01
    assert flagged[0].ratio > 3.0
    # every step's wall time made it into the metrics log
    walls = [m["wall_s"] for m in tr.metrics_log if "wall_s" in m]
    assert len(walls) == 5
    assert walls[2] > 0.05


def test_trainer_wires_slack_and_prediction_into_monitor(tmp_path):
    run = _tiny_run(tmp_path, straggler_slack=4.5)
    tr = Trainer(run, mesh=None, predicted_step_s=0.25)
    assert tr.monitor.slack == 4.5
    assert tr.monitor.predicted_step_s == 0.25
    # without a model prediction the monitor starts expectation-less
    tr2 = Trainer(run, mesh=None)
    assert tr2.monitor.predicted_step_s is None
    assert tr2.monitor.expectation() is None


# ---------------------------------------------------------------------------
# sharded training on a (data 2, model 2) mesh of virtual CPU devices
# ---------------------------------------------------------------------------

_SHARDED = r"""
import os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
sys.path.insert(0, "src")
from repro.launch.mesh import make_host_mesh
from repro.launch.train import run_config
from repro.runtime import Trainer

run = run_config("xlstm-125m", smoke=True, steps=3, seq_len=32, batch=4,
                 ckpt_dir=tempfile.mkdtemp())
trainer = Trainer(run, mesh=make_host_mesh(model=2))
state = trainer.init_state(0)
for p, m in zip(jax.tree.leaves(state.params),
                jax.tree.leaves(state.opt_state.mu)):
    assert m.sharding == p.sharding, (m.sharding, p.sharding)
state = trainer.train(state, 3, log_every=0)
assert not [r for r in trainer.metrics_log if r.get("event")], \
    trainer.metrics_log
print("TRACES", trainer._train_step._cache_size())
"""


def test_sharded_steps_keep_their_layout_and_compile_once():
    """Optimizer moments are born with their parameter's sharding, and a
    step returns its state in the layout the next step takes: three steps
    run with one compiled program and no replayed step."""
    import os
    import subprocess
    import sys

    out = subprocess.run([sys.executable, "-c", _SHARDED],
                         capture_output=True, text=True, cwd=os.getcwd(),
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TRACES 1" in out.stdout, out.stdout
