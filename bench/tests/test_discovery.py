"""The harness finds configurations, traffic mixes, limits and metric
readers by name, so a new one is new files only; and BENCHMARK.json keeps
to the benchmark's naming rules."""
import json
import re
import shutil
from types import SimpleNamespace

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of bench/ that the harness reads instead of the real one."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench")
    shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    monkeypatch.setattr(harness, "ROOT", root)
    return root


def test_new_files_are_found_by_name(bench_copy):
    b = bench_copy / "bench"
    cfg = dict(harness.config("quclassi-7q-3l"), name="probe-cfg", n_layers=2)
    (b / "configs" / "probe-cfg.json").write_text(json.dumps(cfg))
    tr = dict(harness.traffic("serve"), rate_per_s=7)
    (b / "traffic" / "probe.mix.json").write_text(json.dumps(tr))
    (b / "limits" / "probe.cell.json").write_text(json.dumps({"fidelity_gap": 1e-3}))
    (b / "metrics" / "probe_metric.serve.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.result['circuits']\n"
    )
    bm = json.loads((bench_copy / "BENCHMARK.json").read_text())
    bm["workloads"].append(
        {"name": "probe.cell", "config": "probe-cfg", "traffic": "probe.mix",
         "chips": 1, "why": "probe"}
    )
    bm["per_layer"].append(
        {"name": "probe_metric.serve", "unit": "x", "better": "higher",
         "source": "program_counter", "layer": "device", "moves": "circuits_per_s",
         "workloads": ["probe.cell"]}
    )
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(bm))

    bm = harness.benchmark()
    cell = harness.workload(bm, "probe.cell")
    assert harness.config(cell["config"])["n_layers"] == 2
    assert harness.traffic(cell["traffic"])["rate_per_s"] == 7
    assert harness.limits(cell["name"]) == {"fidelity_gap": 1e-3}
    assert harness.kind(harness.traffic(cell["traffic"])["kind"]).WINDOW_SPAN
    specs = harness.cell_metrics(bm, "probe.cell", trace=True)
    assert [m["name"] for m in specs] == ["probe_metric.serve"]
    ctx = SimpleNamespace(result={"circuits": 3})
    assert harness.read_metrics(specs, ctx) == {
        "probe_metric.serve": {"value": 6.0, "unit": "x"}
    }
    e2e_names = [m["name"] for m in harness.cell_metrics(bm, "probe.cell", trace=False)]
    assert e2e_names == ["circuits_per_s", "setup_s"]


def test_reader_that_finds_nothing_ends_the_run(bench_copy):
    """A metric the cell has to report that reads nothing (a kernel name
    the trace no longer has, say) is an error, not a metric left out."""
    specs = [{"name": "rows_roofline", "unit": "%"}]
    ctx = SimpleNamespace(result={"kernel_work": {}}, trace=None, cell={"name": "probe"})
    with pytest.raises(SystemExit, match="rows_roofline"):
        harness.read_metrics(specs, ctx)


def test_every_file_the_benchmark_names_exists():
    bm = harness.benchmark()
    for c in bm["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert harness.config(c["name"])["name"] == c["name"]
    for w in bm["workloads"]:
        harness.traffic(w["traffic"])
        harness.limits(w["name"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_names_and_units_keep_to_the_rules():
    bm = harness.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    names = []
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names.append(w["name"])
    cells = {w["name"] for w in bm["workloads"]}
    e2e = {m["name"] for m in bm["end_to_end"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m) - {"workloads"} <= {"name", "unit", "better", "bound", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        names.append(m["name"])
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert len(json.dumps(bm)) < 64 * 1024
