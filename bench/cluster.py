"""What the traffic kinds share: the cluster a configuration stands for,
its QuClassi model, and the program's counters read as window deltas."""
from __future__ import annotations


def qcfg(cfg: dict):
    """The program's ``QuClassiConfig`` for a configuration file."""
    from repro.core.quclassi import QuClassiConfig
    from repro.core.segmentation import SegmentationConfig

    return QuClassiConfig(
        qc=cfg["n_qubits"],
        n_layers=cfg["n_layers"],
        n_classes=cfg["n_classes"],
        seg=SegmentationConfig(
            filter_width=cfg["filter_width"], stride=cfg["stride"], n_filters=4
        ),
        image_size=(cfg["image_size"], cfg["image_size"]),
        use_dense=cfg["use_dense"],
    )


def cluster(cfg: dict, tr: dict):
    """A ``QuantumCluster`` on the configuration's fleet, with the traffic
    mix's serving settings."""
    from repro.api import ClusterConfig, QuantumCluster, ServingConfig
    from repro.comanager.worker import WorkerConfig

    workers = tuple(
        WorkerConfig(f"w{i + 1}", q) for i, q in enumerate(cfg["fleet_qubits"])
    )
    return QuantumCluster(
        ClusterConfig(workers=workers, serving=ServingConfig(**tr["serving"]))
    )


def telemetry(cl) -> dict:
    """The counters and stage histograms the per-layer metrics read."""
    tel = cl.telemetry
    hists = tel.trace.stage_hists
    return {
        "batched_circuits": tel.batched_circuits,
        "padded_lanes": tel.padded_lanes,
        "fused_launches": tel.fused_launches,
        "fused_banks": tel.fused_banks,
        "multibank_launches": tel.multibank_launches,
        "mesh_spills": tel.mesh_spills,
        "launch_modes": dict(tel.trace.kernel_launches),
        "stages": {k: (h.total, h.count) for k, h in hists.items()},
    }


def telemetry_delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in after if k not in ("stages", "launch_modes")}
    out["launch_modes"] = {
        k: n - before["launch_modes"].get(k, 0) for k, n in after["launch_modes"].items()
    }
    out["stages"] = {
        k: (t - before["stages"].get(k, (0.0, 0))[0], n - before["stages"].get(k, (0.0, 0))[1])
        for k, (t, n) in after["stages"].items()
    }
    return out
