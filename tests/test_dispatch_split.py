"""How ``execute_batch`` cuts a shift-group launch's output into member
values: one compiled split per bank block, whose values are the very bits
a per-member index of the same output gives, whose eager dispatches do not
grow with the bank's group count, and whose programs are keyed on the
block's shape alone, never on which groups a batch carries."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import dispatch

from repro.core import shift_rule
from repro.core.quclassi import QuClassiConfig
from repro.kernels import ops as kops
from repro.serve import (
    CircuitFuture,
    CoalescedBatch,
    GatewayRuntime,
    PendingCircuit,
    ShiftGroupKey,
    bank_partition,
    execute_batch,
)


def make_banks(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [
        shift_rule.build_shift_bank(
            jnp.asarray(rng.uniform(0, np.pi, cfg.n_theta), jnp.float32),
            jnp.asarray(rng.uniform(0, np.pi, (b, cfg.n_angles)), jnp.float32),
        )
        for b in sizes
    ]


def make_batch(key, banks, picks, futures=False):
    """A batch whose members are groups ``g`` of banks ``k`` for each
    ``(k, g)`` in ``picks``, in that order."""
    members = [
        PendingCircuit(
            key,
            "t",
            seq,
            0.0,
            (banks[k], g),
            future=CircuitFuture("t", seq, 0.0) if futures else None,
            lanes=banks[k].n_samples,
        )
        for seq, (k, g) in enumerate(picks)
    ]
    return CoalescedBatch(key=key, members=members, created=0.0)


def recording(fn, launched):
    """``fn``, keeping each output it returns in ``launched``."""

    def run(*args):
        out = fn(*args)
        launched.append(out)
        return out

    return run


def indexed(out, batch):
    """The parent's member values: one eager index per member of the
    launch output (a single bank's ``(G, B)`` rows, or a tuple of per-bank
    blocks cut to each bank's true sample count)."""
    banks, _, slots = bank_partition(batch)
    if isinstance(out, tuple):
        return [out[k][i][: banks[k].n_samples] for k, i in slots]
    return [out[i] for _, i in slots]


CASES = {
    # (bank sample counts, member picks (bank, group) in batch order, spill)
    "one_bank_all_groups": ((8,), "all", False),
    "one_bank_subset_scrambled": ((8,), ((0, -1), (0, 3), (0, 0), (0, 5), (0, 2)), False),
    "two_banks_unequal": (
        (128, 5),
        ((1, 2), (0, 0), (1, -1), (0, 4), (1, 0), (0, -1)),
        False,
    ),
    "spilled_one_bank": ((8,), ((0, 1), (0, -1), (0, 0)), True),
    "spilled_two_banks": ((128, 5), ((0, 3), (1, 1), (1, -1), (0, 0)), True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_split_values_equal_per_member_indexing(case):
    """Every member's value is bit-identical to an eager index of its row
    in the same launch output, on the worker path (single- and multi-bank
    launches, any subset of groups in any order) and on ``run_spilled``'s
    whole-mesh path."""
    sizes, picks, spill = CASES[case]
    cfg = QuClassiConfig(qc=5, n_layers=1)
    key = ShiftGroupKey(cfg.spec, False)
    banks = make_banks(cfg, sizes)
    n = banks[0].n_groups
    if picks == "all":
        picks = [(0, g) for g in range(n)]
    batch = make_batch(key, banks, [(k, g % n) for k, g in picks], futures=spill)
    launched = []
    if spill:
        rt = GatewayRuntime(mode="sync")
        try:
            rt.gateway.register_client("t")
            ex = rt.dispatcher._spill_executor()
            ex.banks = recording(ex.banks, launched)
            assert rt.dispatcher.run_spilled(batch) == "mesh"
        finally:
            rt.close()
        got = [m.future.result(timeout=60.0) for m in batch.members]
        (out,) = launched
        # the spill runner's single-bank path takes block 0 of a bank tuple
        want = indexed(out if len(banks) > 1 else out[0], batch)
    else:
        got = execute_batch(
            batch,
            kops.vqc_fidelity,
            recording(kops.vqc_fidelity_shiftgroups, launched),
            recording(kops.vqc_fidelity_shiftgroups_multibank, launched),
        )
        (out,) = launched
        want = indexed(out, batch)
    assert len(got) == len(want) == len(picks)
    for g, w, m in zip(got, want, batch.members):
        assert isinstance(g, jax.Array)
        assert g.shape == (m.payload[0].n_samples,)
        assert np.array_equal(np.asarray(g), np.asarray(w))


@functools.partial(jax.jit, static_argnums=2)
def _stub_launch(theta, data, n_groups):
    """A stand-in kernel: a ``(G, B)`` block that depends on every input."""
    lanes = theta.sum(1) + data.sum(1)
    return jnp.arange(n_groups, dtype=jnp.float32)[:, None] + lanes[None, :]


def _stub_kernel(spec, theta, data, four_term, groups):
    return _stub_launch(theta, data, len(groups))


def _stub_multibank(spec, thetas, datas, four_term, group_sets):
    return tuple(_stub_launch(t, d, len(gs)) for t, d, gs in zip(thetas, datas, group_sets))


def test_split_dispatches_do_not_grow_with_groups(monkeypatch):
    """Cutting a launch's output into members costs the same few eager
    dispatches for a 29-group (7 qubits) and an 89-group (17 qubits) bank,
    single- and two-bank alike; a batch of the same shapes that carries
    another split of the groups compiles nothing."""
    eager = []
    fetch = dispatch.xla_primitive_callable

    def counted(prim, **params):
        eager.append(prim.name)
        return fetch(prim, **params)

    counts = {}
    for qc, images in ((7, 16), (17, 64)):
        cfg = QuClassiConfig(qc=qc, n_layers=3)
        key = ShiftGroupKey(cfg.spec, False)
        banks = make_banks(cfg, (9 * images, 9 * images))
        n = banks[0].n_groups
        one = make_batch(key, banks[:1], [(0, g) for g in range(n)])
        two = make_batch(key, banks, [(k, g) for g in range(n) for k in (0, 1)])

        def run(batch):
            out = execute_batch(batch, kops.vqc_fidelity, _stub_kernel, _stub_multibank)
            jax.block_until_ready(out)
            return out

        run(one), run(two)  # compile outside the counted calls
        monkeypatch.setattr(dispatch, "xla_primitive_callable", counted)
        try:
            del eager[:]
            run(one)
            single = len(eager)
            del eager[:]
            run(two)
            counts[n] = (single, len(eager))
        finally:
            monkeypatch.setattr(dispatch, "xla_primitive_callable", fetch)

        compiles = []

        def on_compile(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(kw.get("fun_name"))

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            part_one = run(make_batch(key, banks[:1], [(0, g) for g in (n - 1, 2, 0)]))
            part_two = run(make_batch(key, banks, [(1, 5), (0, n - 1), (1, 0)]))
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)
        assert compiles == []
        assert len(part_one) == 3 and len(part_two) == 3
    assert set(counts) == {29, 89}
    assert counts[29] == counts[89]
    assert max(counts[29]) <= 2
