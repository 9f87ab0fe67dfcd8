"""The paper's own workload configs: QuClassi quantum-classical CNN at the
evaluated qubit/layer settings (§IV-A) — registered alongside the classical
zoo so the launcher can `--arch quclassi-5q-1l` etc.

``quclassi-17q-3l`` is QuClassi at its own published feature width (Stein
et al., arXiv:2103.11307): a 4x4 patch's 16 features, two angles per
qubit, on an 8-qubit data register, a matching trainable register and the
SWAP-test ancilla.  Only the 20-qubit worker of the paper's
5/10/15/20-qubit fleet can take it, and its shift banks run on the
depth-tiled spill kernel."""
from repro.core.quclassi import QuClassiConfig
from repro.core.segmentation import SegmentationConfig

QUCLASSI_CONFIGS: dict[str, QuClassiConfig] = {}

for qc, layers in ((5, (1, 2, 3)), (7, (1, 2, 3)), (17, (3,))):
    for nl in layers:
        QUCLASSI_CONFIGS[f"quclassi-{qc}q-{nl}l"] = QuClassiConfig(
            qc=qc, n_layers=nl,
            seg=SegmentationConfig(filter_width=4, stride=2, n_filters=4),
            image_size=(8, 8),
        )


def get_quclassi(name: str) -> QuClassiConfig:
    return QUCLASSI_CONFIGS[name]
