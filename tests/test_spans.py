"""Program spans and the compile counter (``repro.obs.spans``).

  * a trainer step through a ``GatewayRuntime`` (sync and async, implicit
    banks) records one ``trainer.step``, one ``trainer.bank`` per class,
    one ``trainer.dense_grad``, two ``trainer.accuracy`` per epoch and at
    least one ``dispatcher.execute`` into the runtime's recorder;
  * a disabled recorder records no span and no compile;
  * a compile inside a span counts once in that span's recorder, one
    outside every span nowhere, and threads do not cross-attribute;
  * the spans reach the profiler's host plane under their own names;
  * ``stage_summary`` gives end-to-end shares for lifecycle stages only.
"""
import glob
import threading

import jax
import numpy as np
import pytest

from repro.obs import ObservabilityConfig, TraceRecorder, span
from repro.obs.spans import COMPILE_HIST


def _count(recorder, name):
    h = recorder.stage_hists.get(name)
    return h.count if h is not None else 0


def _total(recorder, name):
    h = recorder.stage_hists.get(name)
    return h.total if h is not None else 0.0


def _compile_fresh_program(size):
    """Compile one new program (a fresh function; numpy input, so that
    making the input compiles nothing)."""
    out = jax.jit(lambda x: x * 2.0 + 1.0)(np.ones(size, np.float32))
    out.block_until_ready()


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_trainer_spans_through_gateway(mode):
    from repro.core import trainer
    from repro.core.quclassi import QuClassiConfig
    from repro.data import mnist
    from repro.serve import GatewayRuntime

    cfg = QuClassiConfig(qc=5, n_layers=1)
    x, y = mnist.make_pair_dataset(3, 9, n_per_class=3, seed=0)
    split = ((x[:4], y[:4]), (x[4:], y[4:]))
    rt = GatewayRuntime(target=128, deadline=0.05, mode=mode)
    try:
        trainer.train(cfg, *split, epochs=1, batch_size=2, lr=0.05,
                      gateway=rt, client_id="t1", bank_mode="implicit", seed=0)
    finally:
        rt.close()
    r = rt.telemetry.trace
    steps = 2
    assert _count(r, "trainer.step") == steps
    assert _count(r, "trainer.bank") == steps * cfg.n_classes
    assert _count(r, "trainer.dense_grad") == steps
    assert _count(r, "trainer.accuracy") == 2
    assert _count(r, "dispatcher.execute") >= 1
    assert _total(r, "trainer.step") >= (
        _total(r, "trainer.bank") + _total(r, "trainer.dense_grad")
    )


def test_disabled_recorder_records_no_span_or_compile():
    for cfg in (ObservabilityConfig(sample_rate=0.0), ObservabilityConfig.disabled()):
        r = TraceRecorder(cfg)
        with span("trainer.step", r):
            with span("trainer.bank"):
                _compile_fresh_program(3)
        assert not r.stage_hists
        assert r.events == 0 and len(r.buffer) == 0
    # no recorder and no open span: the span only annotates
    with span("trainer.step") as s:
        assert s.recorder is None


def test_compile_counted_in_innermost_span_only():
    r = TraceRecorder()
    with span("trainer.step", r):
        with span("trainer.dense_grad"):
            _compile_fresh_program(5)
    assert _count(r, COMPILE_HIST) == 1
    assert _count(r, "trainer.step") == 1 and _count(r, "trainer.dense_grad") == 1
    _compile_fresh_program(6)  # outside every span
    assert _count(r, COMPILE_HIST) == 1

    # two threads, two recorders, compiling at the same time
    recs = [TraceRecorder(), TraceRecorder()]
    barrier = threading.Barrier(2)
    errors = []

    def work(i):
        try:
            with span("dispatcher.execute", recs[i]):
                barrier.wait(timeout=30)
                _compile_fresh_program(7 + i)
                barrier.wait(timeout=30)
        except BaseException as exc:  # reported by the test's thread
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errors
    assert [_count(rec, COMPILE_HIST) for rec in recs] == [1, 1]
    assert _count(r, COMPILE_HIST) == 1


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    names = ("trainer.step", "trainer.bank", "dispatcher.execute")
    r = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span(names[0], r):
            with span(names[1]):
                x = jax.numpy.ones(4) + 1
                x.block_until_ready()

        def worker():
            with span(names[2], r):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                seen.update(e.name for e in line.events)
    assert set(names) <= seen


def test_stage_summary_shares_only_for_lifecycle_stages():
    r = TraceRecorder()
    r.circuit_submit(0, "t", "k", 0.0)
    r.circuit_stage(0, "admit", 0.1)
    r.circuit_end(0, "complete", 0.4)
    events = r.events
    r.record_span("trainer.step", 2.0)
    r.record_span(COMPILE_HIST, 0.5)
    # spans are histograms only: no event, no trace-buffer record
    assert r.events == events and len(r.buffer) == 1
    out = r.stage_summary()
    assert out["trainer.step"]["count"] == 1 and out[COMPILE_HIST]["count"] == 1
    assert out["queue_wait_share"] == pytest.approx(0.25)
    assert out["execute_share"] == pytest.approx(0.75)
    assert not [k for k in out if k.endswith("_share")
                and k not in ("queue_wait_share", "execute_share")]
