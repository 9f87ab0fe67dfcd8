"""The readers of the program spans and the compile counter, on a
hand-built window: their values, nothing to read when the program records
no ``trainer.step``, and no compile read as 0."""
from types import SimpleNamespace

import pytest

from bench import harness

#: window deltas as ``bench/cluster.telemetry_delta`` gives them:
#: (total seconds, count) per ``stage_hists`` entry
STAGES = {
    "trainer.step": (30.0, 2),
    "trainer.bank": (16.0, 4),
    "trainer.dense_grad": (6.0, 2),
    "trainer.accuracy": (12.0, 4),
    "dispatcher.execute": (0.5, 10),
    "compile": (1.5, 3),
    "coalesce_wait": (2.0, 100),
}

EXPECTED = {
    "step_self_ms.train": 1e3 * (30.0 - 16.0 - 6.0) / 2,
    "bank_wait_ms.train": 1e3 * 16.0 / 4,
    "dense_grad_ms.train": 1e3 * 6.0 / 2,
    "accuracy_ms.train": 1e3 * 12.0 / 4,
    "execute_ms.train": 1e3 * 0.5 / 10,
    "window_compiles.train": 3,
}


def _ctx(stages):
    return SimpleNamespace(result={"telemetry": {"stages": stages}})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_values(name):
    assert harness.metric_reader(name).read(_ctx(STAGES)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_to_read_without_trainer_step(name):
    """A program without the spans reads nothing, whatever else its
    histograms hold."""
    stages = {k: v for k, v in STAGES.items() if k != "trainer.step"}
    assert harness.metric_reader(name).read(_ctx(stages)) is None
    assert harness.metric_reader(name).read(_ctx({"coalesce_wait": (2.0, 100)})) is None


def test_no_compile_reads_zero():
    stages = {k: v for k, v in STAGES.items() if k != "compile"}
    assert harness.metric_reader("window_compiles.train").read(_ctx(stages)) == 0
