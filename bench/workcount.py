"""Operations and bytes the QuClassi work needs, independent of how the
program computes it.

A circuit of n = 2m + 1 qubits is two m-qubit registers joined by a SWAP
test, so its work is counted on 2**m-amplitude registers:

* every gate costs ``GATE_OPS_PER_AMP`` f32 operations per amplitude of the
  register it acts on: an output amplitude is a 2-term complex linear
  combination, two complex products (6 operations each) and one complex
  addition (2);
* the SWAP test costs one 2**m-amplitude inner product,
  ``INNER_OPS_PER_AMP`` operations per amplitude: a complex product and a
  complex accumulation.

A parameter-shift bank counts lanes x groups circuits, each a whole
circuit: prefix reuse, replay or another gate lowering changes the time a
bank takes and never its count.

Bytes are the call's HBM inputs and outputs in f32: each lane's angles in,
one fidelity per circuit out; a spilled bank also writes and reads the
states it keeps in HBM, two f32 planes of 2**m amplitudes per lane per
state.  The roofline time of a call is max(ops / peak ops, bytes / peak
bytes); ``bound`` says which of the two it is.
"""
from __future__ import annotations

GATE_OPS_PER_AMP = 14
INNER_OPS_PER_AMP = 8
F32 = 4


def register_amps(n_qubits: int) -> int:
    return 2 ** ((n_qubits - 1) // 2)


def circuit_ops(n_qubits: int, n_theta: int, n_data: int) -> int:
    """f32 operations of one circuit: one gate per angle, then the test."""
    return register_amps(n_qubits) * (GATE_OPS_PER_AMP * (n_theta + n_data) + INNER_OPS_PER_AMP)


def rows_work(cfg: dict, circuits: int) -> dict:
    """Row circuits: each lane reads its own angles and writes one fidelity."""
    per_in = (cfg["n_theta"] + cfg["n_data"]) * F32
    return {
        "circuits": circuits,
        "ops": circuits * circuit_ops(cfg["n_qubits"], cfg["n_theta"], cfg["n_data"]),
        "bytes": circuits * (per_in + F32),
    }


def bank_work(cfg: dict, lanes: int, groups: int, spill_states: int = 0) -> dict:
    """One implicit parameter-shift bank: ``lanes`` samples x ``groups``
    (param, shift) groups; ``spill_states`` states per lane written to HBM
    and read back when the register is spilled in depth tiles."""
    circuits = lanes * groups
    state = 2 * register_amps(cfg["n_qubits"]) * F32
    return {
        "circuits": circuits,
        "ops": circuits * circuit_ops(cfg["n_qubits"], cfg["n_theta"], cfg["n_data"]),
        "bytes": lanes * (cfg["n_theta"] + cfg["n_data"]) * F32
        + circuits * F32
        + 2 * spill_states * lanes * state,
    }


def roofline_s(work: dict, peak: dict) -> float:
    return max(work["ops"] / peak["flops_per_s"], work["bytes"] / peak["hbm_bytes_per_s"])


def bound(work: dict, peak: dict) -> str:
    ops_s = work["ops"] / peak["flops_per_s"]
    return "ops" if ops_s >= work["bytes"] / peak["hbm_bytes_per_s"] else "bytes"
