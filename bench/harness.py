"""What every cell shares: finding its files by name, the device check,
the compile cache, seeds, and the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
and a traffic mix.  Each is a file of its own, found by name:

  bench/configs/<config>.json    sizes, source, reduced/assumed keys
  bench/traffic/<traffic>.json   the mix; its ``kind`` names the module
                                 under bench/kinds/ that generates it
  bench/limits/<workload>.json   the limit of each number ``correct`` compares
  bench/metrics/<metric>.py      one reader per metric: ``read(ctx)``

so a later change adds a cell, a mix or a metric as new files only.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(name: str) -> dict:
    return load_json(BENCH / "limits" / f"{name}.json")


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise SystemExit(f"bench: no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(name: str):
    """The module that generates traffic of this kind (``bench/kinds/``)."""
    return _load_module(BENCH / "kinds" / f"{name}.py", f"bench_kind_{name}")


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def cell_metrics(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in bm["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m
        for m in bm["per_layer"]
        if m["moves"] in moved and cell in m.get("workloads", [cell])
    ]


def peak(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench: device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def sub_seed(seed: int, *tags: int) -> int:
    """A seed in [0, 2**30) for a part of the run, drawn from the run's seed
    (any non-negative integer) and the part's tags."""
    import numpy as np

    return int(np.random.default_rng([seed, *tags]).integers(0, 2**30))


def use_program() -> None:
    """Put the program under test (``src/``) on the path; exit when the
    checkout does not hold it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"bench: no program under {src}")
    sys.path.insert(0, str(src))


def require_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    """The program's persistent cache (``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``), keeping every program it compiles
    so that only a checkout's first run of a cell compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs the process had to get (``programs``: new to its
    in-memory cache), and of those, the ones compiled afresh
    (``compiles``: persistent-cache misses) and loaded from disk."""

    def __init__(self):
        import jax

        self.programs = 0
        self.compiles = 0
        self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.programs, self.compiles, self.loaded


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) count."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))]


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def read_metrics(specs: list[dict], ctx: SimpleNamespace) -> dict:
    """Each metric's reader, by name.  A reader that finds nothing to read
    returns None; since ``specs`` holds only the metrics this cell has to
    report, that is an error here, and the run ends without a result
    rather than leave the metric out in silence."""
    out, missing = {}, []
    for m in specs:
        value = metric_reader(m["name"]).read(ctx)
        if value is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    if missing:
        raise SystemExit(f"bench: {ctx.cell['name']} found nothing to read for {missing}")
    return out


def print_result(line: dict, checks: list[tuple[str, float, float]]) -> None:
    """Each number compared beside its limit, as the last lines of standard
    error and under ``checks``, the last key of the result line."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} limit {limit!r}", file=sys.stderr, flush=True)
    line = dict(line, checks={n: {"value": v, "limit": lim} for n, v, lim in checks})
    print(json.dumps(line), flush=True)
