"""Shift-structured circuit-bank execution: implicit ``ShiftBank``s, the
prefix-reuse kernel, group-scheduled data-plane executors, and the serving
gateway's per-(param, shift)-group path.

Correctness contract: everything here must agree with the MATERIALIZED bank
(``build_bank`` + the standard fused kernel / dense-sim oracle) — scheduling
and the shift-structured execution strategy never change the math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import circuits, shift_rule
from repro.core.sim import CircuitSpec, Op
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels import vqc_statevector as K


def _setup(qc, nl, b=3, seed=0):
    spec = circuits.build_quclassi_circuit(qc, nl)
    key = jax.random.PRNGKey(seed)
    theta = jax.random.uniform(key, (spec.n_theta,), jnp.float32,
                               minval=0.0, maxval=np.pi)
    data = jax.random.uniform(jax.random.fold_in(key, 1), (b, spec.n_data),
                              jnp.float32, minval=0.0, maxval=np.pi)
    return spec, theta, data


# ------------------------------------------------------------ ShiftBank
@pytest.mark.parametrize("qc,nl", [(5, 1), (5, 3), (7, 1), (7, 3)])
@pytest.mark.parametrize("four_term", [False, True])
@pytest.mark.parametrize("seed", [0, 7])
def test_materialize_reproduces_build_bank_exactly(qc, nl, four_term, seed):
    """The escape hatch is BIT-identical to build_bank, not just close."""
    spec, theta, data = _setup(qc, nl, b=4, seed=seed)
    implicit = shift_rule.build_shift_bank(theta, data, four_term=four_term)
    explicit = shift_rule.build_bank(theta, data, four_term=four_term)
    mat = implicit.materialize()
    assert np.array_equal(np.asarray(mat.theta), np.asarray(explicit.theta))
    assert np.array_equal(np.asarray(mat.data), np.asarray(explicit.data))
    assert (mat.n_samples, mat.n_params, mat.four_term) == \
        (explicit.n_samples, explicit.n_params, explicit.four_term)


def test_shiftbank_bookkeeping_matches_circuitbank():
    spec, theta, data = _setup(5, 2, b=3)
    bank = shift_rule.build_shift_bank(theta, data)
    assert bank.n_groups == 1 + 2 * spec.n_theta
    assert bank.n_circuits == bank.n_groups * 3
    f = jnp.arange(bank.n_circuits, dtype=jnp.float32)
    for got, want in zip(bank.split_results(f),
                         bank.materialize().split_results(f)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    descs = bank.group_descriptors()
    assert descs[0] == (-1, 0.0)
    assert len(descs) == bank.n_groups
    assert descs[1][0] == 0 and descs[1][1] == pytest.approx(np.pi / 2)
    assert descs[1 + spec.n_theta][1] == pytest.approx(-np.pi / 2)


def test_per_sample_theta_shiftbank():
    """ShiftBank generalizes build_bank: per-sample base thetas are allowed."""
    spec, _, data = _setup(5, 1, b=4)
    theta = jax.random.uniform(jax.random.PRNGKey(3), (4, spec.n_theta),
                               jnp.float32, minval=0.0, maxval=np.pi)
    bank = shift_rule.build_shift_bank(theta, data)
    mat = bank.materialize()
    j, b = 2, 1
    row = np.asarray(mat.theta[4 + j * 4 + b])
    expect = np.asarray(theta[b]).copy()
    expect[j] += np.pi / 2
    np.testing.assert_allclose(row, expect, atol=1e-6)
    got = kops.vqc_fidelity_shiftbank(spec, bank.theta, bank.data)
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ------------------------------------------------- prefix-reuse kernel
@pytest.mark.parametrize("qc", [5, 7])
@pytest.mark.parametrize("nl", [1, 3])
@pytest.mark.parametrize("four_term", [False, True])
def test_prefix_reuse_matches_ref(qc, nl, four_term):
    spec, theta, data = _setup(qc, nl, b=3, seed=qc * 10 + nl)
    bank = shift_rule.build_shift_bank(theta, data, four_term=four_term)
    mat = bank.materialize()
    got = kops.vqc_fidelity_shiftbank(spec, bank.theta, bank.data, four_term)
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data)
    assert got.shape == (bank.n_circuits,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_group_subset_matches_full():
    spec, theta, data = _setup(5, 3, b=4)
    bank = shift_rule.build_shift_bank(theta, data)
    full = np.asarray(kops.vqc_fidelity_shiftgroups(spec, bank.theta,
                                                    bank.data))
    groups = (0, 2, 5, bank.n_groups - 1)
    sub = np.asarray(kops.vqc_fidelity_shiftgroups(spec, bank.theta,
                                                   bank.data, False, groups))
    np.testing.assert_allclose(sub, full[list(groups)], atol=1e-6)


def test_shift_plan_structure():
    spec = circuits.build_quclassi_circuit(7, 3)
    plan = K.build_shift_plan(spec)
    assert plan is not None
    m = (7 - 1) // 2
    assert plan.m == m
    assert len(plan.data_ops) == spec.n_data
    assert len(plan.train_ops) == spec.n_theta
    # every parameter has a unique dependent gate, in circuit order
    assert plan.theta_pos == tuple(range(spec.n_theta))


def test_shift_plan_rejects_unstructured_circuits():
    # no SWAP-test tail -> no product structure to exploit
    spec = CircuitSpec(n_qubits=2, ops=(Op("ry", (0,), ("theta", 0)),
                                        Op("ry", (1,), ("data", 0))),
                       n_theta=1, n_data=1)
    assert K.build_shift_plan(spec) is None
    # fallback path still produces correct bank fidelities
    theta = jnp.asarray([[0.3], [0.9]], jnp.float32)
    data = jnp.asarray([[0.1], [0.4]], jnp.float32)
    bank = shift_rule.build_shift_bank(theta, data)
    got = kops.vqc_fidelity_shiftbank(spec, bank.theta, bank.data)
    mat = bank.materialize()
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_shift_bank_stats_acceptance_ratios():
    """The paper's 7q/3l config: >=5x fewer gate applications, >=10x fewer
    angle bytes than the materialized bank (ISSUE acceptance)."""
    spec = circuits.build_quclassi_circuit(7, 3)
    stats = K.shift_bank_stats(spec, n_samples=64)
    assert stats["gate_apps_ratio"] >= 5.0
    assert stats["angle_bytes_ratio"] >= 10.0


# -------------------------------------------- descending two-qubit pairs
def test_rot2_descending_symmetric_pairs():
    """RYY/RZZ are symmetric under qubit exchange; the kernel now accepts
    descending pairs instead of raising (satellite fix)."""
    ops_desc = (Op("ry", (0,), ("data", 0)), Op("ryy", (1, 0), ("theta", 0)),
                Op("rzz", (2, 1), ("theta", 1)))
    ops_asc = (Op("ry", (0,), ("data", 0)), Op("ryy", (0, 1), ("theta", 0)),
               Op("rzz", (1, 2), ("theta", 1)))
    sd = CircuitSpec(n_qubits=3, ops=ops_desc, n_theta=2, n_data=1)
    sa = CircuitSpec(n_qubits=3, ops=ops_asc, n_theta=2, n_data=1)
    theta = jnp.asarray([[0.7, 1.1], [0.2, 2.0]], jnp.float32)
    data = jnp.asarray([[0.5], [1.3]], jnp.float32)
    re_d, im_d = kops.vqc_state(sd, theta, data)
    re_a, im_a = kops.vqc_state(sa, theta, data)
    np.testing.assert_allclose(np.asarray(re_d), np.asarray(re_a), atol=1e-6)
    np.testing.assert_allclose(np.asarray(im_d), np.asarray(im_a), atol=1e-6)
    # and against the dense-sim oracle, which permutes axes generically
    re_r, im_r = ref.vqc_state_ref(sd, theta, data)
    np.testing.assert_allclose(np.asarray(re_d), np.asarray(re_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(im_d), np.asarray(im_r), atol=1e-5)


def test_rot2_descending_controlled_still_raises():
    ops_bad = (Op("cry", (1, 0), ("theta", 0)),)
    spec = CircuitSpec(n_qubits=2, ops=ops_bad, n_theta=1, n_data=0)
    theta = jnp.asarray([[0.7]], jnp.float32)
    data = jnp.zeros((1, 0), jnp.float32)
    with pytest.raises(NotImplementedError):
        kops.vqc_state(spec, theta, data)


# -------------------------------------------------- gradient equivalence
#: agreement of two f32 simulations of the same circuits (kernel vs
#: core.sim, or jitted vs eager core.sim): they differ in the last ulps.
FID_ATOL = 1e-6


def _assert_bce_close(got, want, fids, atol):
    """Loss and gradient agreement, scaled by the BCE chain's conditioning.

    dL/dF = (F - y) / (F (1 - F)) amplifies a fidelity error by up to
    1 / (F (1 - F)).  Random angles put some samples at F ~ 1e-4, where the
    ~1e-7 last-ulp gap in F becomes a ~1e-3 relative gap in loss and
    gradient.  So both keep their absolute bound ``atol`` and are allowed
    FID_ATOL times that amplification on top, relative — the fidelities
    themselves are asserted to FID_ATOL."""
    f = np.asarray(fids, np.float64)
    rtol = FID_ATOL * float(np.max(1.0 / (f * (1.0 - f))))
    (l_got, g_got), (l_want, g_want) = got, want
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("qc,nl,exact", [(5, 1, False), (5, 3, True),
                                         (7, 2, False)])
def test_parameter_shift_grad_implicit_vs_materialized(qc, nl, exact):
    spec, theta, data = _setup(qc, nl, b=3, seed=nl)
    labels = jnp.asarray([0.0, 1.0, 1.0])
    l_mat, g_mat, f_mat = shift_rule.parameter_shift_grad(
        spec, theta, data, labels, exact_controlled=exact)
    l_imp, g_imp, f_imp = shift_rule.parameter_shift_grad(
        spec, theta, data, labels, executor=kops.shiftbank_executor(spec),
        exact_controlled=exact)
    np.testing.assert_allclose(np.asarray(f_imp), np.asarray(f_mat),
                               atol=FID_ATOL)
    _assert_bce_close((l_imp, g_imp), (l_mat, g_mat), f_mat, atol=1e-5)


def test_implicit_flag_with_shift_unaware_executor():
    """implicit=True + a plain (theta, data) executor goes through
    materialize() — the compatibility escape hatch."""
    spec, theta, data = _setup(5, 1, b=2)
    labels = jnp.asarray([1.0, 0.0])
    seen = {}

    def executor(t, d):
        seen["shape"] = (t.shape, d.shape)
        from repro.core import fidelity as fid
        return fid.fidelity_batch(spec, t, d)

    l1, g1, f1 = shift_rule.parameter_shift_grad(spec, theta, data, labels,
                                                 executor=executor,
                                                 implicit=True)
    c = 2 * (2 * spec.n_theta + 1)
    assert seen["shape"] == ((c, spec.n_theta), (c, spec.n_data))
    l0, g0, f0 = shift_rule.parameter_shift_grad(spec, theta, data, labels)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f0), atol=FID_ATOL)
    _assert_bce_close((l1, g1), (l0, g0), f0, atol=1e-6)


# ------------------------------------------------- data-plane executors
def test_worker_batched_group_assignment():
    from repro.comanager import dataplane
    spec, theta, data = _setup(5, 2, b=5)
    bank = shift_rule.build_shift_bank(theta, data)
    assignment = dataplane.round_robin_assignment(bank.n_groups, 3)
    run = dataplane.worker_batched_executor(spec, assignment, 3)
    assert run.accepts_shiftbank
    got = run(bank)
    mat = bank.materialize()
    want = kops.vqc_fidelity(spec, mat.theta, mat.data)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_worker_batched_row_assignment_accepts_implicit_bank():
    """Legacy per-row assignments still work on implicit banks (materialize
    fallback preserves exact per-worker row placement)."""
    from repro.comanager import dataplane
    spec, theta, data = _setup(5, 1, b=4)
    bank = shift_rule.build_shift_bank(theta, data)
    assignment = dataplane.round_robin_assignment(bank.n_circuits, 2)
    run = dataplane.worker_batched_executor(spec, assignment, 2)
    mat = bank.materialize()
    np.testing.assert_allclose(np.asarray(run(bank)),
                               np.asarray(run(mat.theta, mat.data)),
                               atol=1e-6)


def test_worker_batched_bad_assignment_length():
    from repro.comanager import dataplane
    spec, theta, data = _setup(5, 1, b=4)
    bank = shift_rule.build_shift_bank(theta, data)
    run = dataplane.worker_batched_executor(spec, [0, 1], 2)
    with pytest.raises(ValueError, match="groups"):
        run(bank)


def test_sharded_executor_accepts_implicit_bank():
    from repro.comanager import dataplane
    from repro.launch.mesh import make_host_mesh
    spec, theta, data = _setup(5, 2, b=5)    # odd B exercises sample padding
    bank = shift_rule.build_shift_bank(theta, data)
    run = dataplane.sharded_executor(spec, make_host_mesh())
    got = run(bank)
    assert got.shape == (bank.n_circuits,)
    mat = bank.materialize()
    want = kops.vqc_fidelity(spec, mat.theta, mat.data)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ------------------------------------------------------ serving gateway
def test_gateway_shift_executor_matches_materialized():
    from repro.serve import GatewayRuntime, ShiftGroupKey
    spec, theta, data = _setup(5, 2, b=4)
    bank = shift_rule.build_shift_bank(theta, data)
    rt = GatewayRuntime()
    run = rt.shift_executor(spec, "tenant-a")
    assert run.accepts_shiftbank
    got = run(bank)
    mat = bank.materialize()
    want = kops.vqc_fidelity(spec, mat.theta, mat.data)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # groups were dispatched as shift-group batches, not per-row circuits
    assert rt.dispatcher.batch_log, "no batches executed"
    total_members = sum(n for (_, n, _) in rt.dispatcher.batch_log)
    assert total_members == bank.n_groups
    # lane-fill telemetry counts the kernel lanes the groups occupy
    # (n_groups * B sample lanes), not the group-subtask member count,
    # and pays per-group row padding (each group pads its B samples
    # independently in the kernel launch)
    assert rt.telemetry.batched_circuits == bank.n_groups * bank.n_samples
    import math
    per_group = math.ceil(bank.n_samples / rt.gateway.coalescer.lanes) * \
        rt.gateway.coalescer.lanes
    assert rt.telemetry.padded_lanes == bank.n_groups * per_group


def test_shift_executors_accept_materialized_banks():
    """Shift-aware executors still take plain (theta, data) calls, so
    bank_mode='materialized' composes with them instead of crashing."""
    from repro.serve import GatewayRuntime
    spec, theta, data = _setup(5, 1, b=3)
    bank = shift_rule.build_shift_bank(theta, data)
    mat = bank.materialize()
    want = np.asarray(kops.vqc_fidelity(spec, mat.theta, mat.data))
    np.testing.assert_allclose(
        np.asarray(kops.shiftbank_executor(spec)(mat.theta, mat.data)),
        want, atol=1e-6)
    rt = GatewayRuntime()
    run = rt.shift_executor(spec, "tenant-a")
    np.testing.assert_allclose(np.asarray(run(mat.theta, mat.data)), want,
                               atol=1e-5)
    # and run_bank routes a materialized CircuitBank through the same path
    np.testing.assert_allclose(
        np.asarray(shift_rule.run_bank(run, mat)), want, atol=1e-5)


def test_gateway_shift_groups_coalesce_across_same_spec_banks():
    """Keys are structural: group subtasks of DIFFERENT banks of the same
    spec + shift rule share a key (they fuse into one multi-bank launch);
    different specs or shift rules never share one."""
    from repro.serve import ShiftGroupKey
    spec, theta, data = _setup(5, 1, b=2)
    other = circuits.build_quclassi_circuit(5, 2)
    assert ShiftGroupKey(spec, False) == ShiftGroupKey(spec, False)
    assert ShiftGroupKey(spec, False) != ShiftGroupKey(spec, True)
    assert ShiftGroupKey(spec, False) != ShiftGroupKey(other, False)


def test_gateway_fuses_same_spec_banks_into_one_launch():
    """Two tenants' banks of one spec coalesce into multi-bank launches:
    fewer kernel launches than banks, results bit-identical to the per-bank
    implicit path."""
    from repro.serve import GatewayRuntime
    spec, theta_a, data = _setup(5, 2, b=4)
    theta_b = theta_a + 0.3
    bank_a = shift_rule.build_shift_bank(theta_a, data)
    bank_b = shift_rule.build_shift_bank(theta_b, data)
    rt = GatewayRuntime(deadline=30.0)
    rt.gateway.register_client("tenant-a")
    rt.gateway.register_client("tenant-b")
    # submit both banks' group subtasks before any drain: one shared buffer
    from repro.serve import ShiftGroupKey
    key = ShiftGroupKey(spec, False)
    futs = []
    for bank in (bank_a, bank_b):
        for g in range(bank.n_groups):
            futs.append(rt.gateway.submit(
                "tenant-a" if bank is bank_a else "tenant-b", key, (bank, g),
                now=rt.dispatcher.clock(), lanes=bank.n_samples))
    rt.dispatcher.drain()
    n = bank_a.n_groups
    got_a = jnp.concatenate([f.value for f in futs[:n]])
    got_b = jnp.concatenate([f.value for f in futs[n:]])
    want_a = kops.vqc_fidelity_shiftbank(spec, bank_a.theta, bank_a.data)
    want_b = kops.vqc_fidelity_shiftbank(spec, bank_b.theta, bank_b.data)
    assert np.array_equal(np.asarray(got_a), np.asarray(want_a))
    assert np.array_equal(np.asarray(got_b), np.asarray(want_b))
    # both banks rode ONE fused launch (2 banks, 1 kernel call)
    assert rt.telemetry.fused_launches == 1
    assert rt.telemetry.fused_banks == 2
    assert rt.telemetry.multibank_launches == 1
    assert len(rt.dispatcher.batch_log) == 1


def test_coalescer_lane_target_flushes_multilane_buffers():
    """target_lanes: a buffer of few multi-lane members (shift-group
    subtasks) size-flushes once its occupied kernel lanes hit the target,
    without waiting for `target` members or the deadline."""
    from repro.serve.coalescer import Coalescer, PendingCircuit
    co = Coalescer(target=128, lanes=128, deadline=100.0, target_lanes=256)
    out = []
    for i in range(5):
        out += co.add(PendingCircuit(key="k", client_id="c", seq=i,
                                     arrival=0.0, payload=None, lanes=64))
    # members 1-4 reach 256 lanes -> one size-triggered batch of 4
    assert len(out) == 1 and out[0].n == 4
    assert co.buffered == 1


def test_grad_shift_through_gateway_shift_executor():
    from repro.core import quclassi
    from repro.core.quclassi import QuClassiConfig
    from repro.data import mnist
    from repro.serve import GatewayRuntime
    cfg = QuClassiConfig(qc=5, n_layers=1)
    x, y = mnist.make_pair_dataset(3, 9, n_per_class=4, seed=0)
    x, y = jnp.asarray(x[:3]), jnp.asarray(y[:3])
    params = quclassi.init_params(cfg, jax.random.PRNGKey(0))
    rt = GatewayRuntime()
    ex = rt.shift_executor(cfg.spec, "trainer")
    l_gw, g_gw, _ = quclassi.grad_shift(cfg, params, x, y, executor=ex)
    l_ref, g_ref, _ = quclassi.grad_shift(cfg, params, x, y)
    np.testing.assert_allclose(float(l_gw), float(l_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_gw["theta"]),
                               np.asarray(g_ref["theta"]), atol=1e-5)


def test_gateway_shift_keys_do_not_leak_coalescer_buffers():
    """Every bank submission mints a fresh ShiftGroupKey; emptied buffers
    must be retired or a long training run grows the coalescer forever."""
    from repro.serve import GatewayRuntime
    spec, theta, data = _setup(5, 1, b=2)
    rt = GatewayRuntime()
    run = rt.shift_executor(spec, "tenant-a")
    for i in range(5):
        run(shift_rule.build_shift_bank(theta + 0.01 * i, data))
    assert len(rt.gateway.coalescer._buffers) == 0


def test_dispatcher_shift_kernel_injectable():
    """GatewayRuntime(shift_kernel=...) substitutes the shift-group runner,
    mirroring the documented KernelFn substitution point."""
    from repro.serve import GatewayRuntime
    spec, theta, data = _setup(5, 1, b=3)
    bank = shift_rule.build_shift_bank(theta, data)
    calls = []

    def stub(s, t, d, four_term, groups):
        calls.append(groups)
        return kops.vqc_fidelity_shiftgroups(s, t, d, four_term, groups)

    rt = GatewayRuntime(shift_kernel=stub)
    run = rt.shift_executor(spec, "tenant-a")
    got = run(bank)
    assert calls and sorted(g for gs in calls for g in gs) == \
        list(range(bank.n_groups))
    mat = bank.materialize()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(kops.vqc_fidelity(spec, mat.theta,
                                                      mat.data)), atol=1e-5)


# -------------------------------------------------- fused multi-bank kernel
def _banks(spec, k, b=3, seed=0, four_term=False):
    key = jax.random.PRNGKey(seed)
    banks = []
    for i in range(k):
        theta = jax.random.uniform(jax.random.fold_in(key, i),
                                   (spec.n_theta,), jnp.float32,
                                   minval=0.0, maxval=np.pi)
        data = jax.random.uniform(jax.random.fold_in(key, 100 + i),
                                  (b + i, spec.n_data), jnp.float32,
                                  minval=0.0, maxval=np.pi)
        banks.append(shift_rule.build_shift_bank(theta, data,
                                                 four_term=four_term))
    return banks


@pytest.mark.parametrize("qc,nl", [(5, 1), (7, 3)])
def test_multibank_kernel_bit_identical_to_per_bank(qc, nl):
    """K same-spec banks fused into one launch: per-bank blocks are
    BIT-identical to K separate prefix-reuse launches (per-lane math is
    untouched by lane packing)."""
    spec = circuits.build_quclassi_circuit(qc, nl)
    banks = _banks(spec, 3, seed=qc)
    outs = kops.vqc_fidelity_shiftgroups_multibank(
        spec, tuple(b.theta for b in banks), tuple(b.data for b in banks),
        False, tuple(tuple(range(b.n_groups)) for b in banks))
    for bank, out in zip(banks, outs):
        ref = kops.vqc_fidelity_shiftgroups(spec, bank.theta, bank.data)
        assert out.shape == ref.shape
        assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_multibank_kernel_partial_group_sets():
    """Banks may request different group subsets; each gets exactly its
    rows, pulled from the union-group fused launch."""
    spec = circuits.build_quclassi_circuit(5, 2)
    banks = _banks(spec, 2)
    gs = ((0, 2, 5), (1, 2, spec.n_theta * 2))
    outs = kops.vqc_fidelity_shiftgroups_multibank(
        spec, tuple(b.theta for b in banks), tuple(b.data for b in banks),
        False, gs)
    for bank, got, groups in zip(banks, outs, gs):
        want = kops.vqc_fidelity_shiftgroups(spec, bank.theta, bank.data,
                                             False, groups)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


def test_multibank_fallback_for_unstructured_spec():
    """No SWAP-test product structure -> per-bank materialized fallback,
    same results (not fused, still correct)."""
    spec = CircuitSpec(n_qubits=2, ops=(Op("ry", (0,), ("theta", 0)),
                                        Op("ry", (1,), ("data", 0))),
                       n_theta=1, n_data=1)
    t1 = jnp.asarray([[0.3], [0.9]], jnp.float32)
    t2 = jnp.asarray([[1.1]], jnp.float32)
    d1 = jnp.asarray([[0.1], [0.4]], jnp.float32)
    d2 = jnp.asarray([[0.8]], jnp.float32)
    outs = kops.vqc_fidelity_shiftgroups_multibank(
        spec, (t1, t2), (d1, d2), False, ((0, 1, 2), (0, 1)))
    np.testing.assert_allclose(
        np.asarray(outs[0]),
        np.asarray(kops.vqc_fidelity_shiftgroups(spec, t1, d1, False,
                                                 (0, 1, 2))), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(outs[1]),
        np.asarray(kops.vqc_fidelity_shiftgroups(spec, t2, d2, False,
                                                 (0, 1))), atol=1e-6)


def test_group_bank_sets_and_run_bank_set():
    spec5 = circuits.build_quclassi_circuit(5, 1)
    spec7 = circuits.build_quclassi_circuit(7, 1)
    b5 = _banks(spec5, 2)
    b7 = _banks(spec7, 1)
    sets = shift_rule.group_bank_sets(
        [(spec5, b5[0]), (spec7, b7[0]), (spec5, b5[1])])
    assert set(sets) == {(spec5, False), (spec7, False)}
    assert sets[(spec5, False)] == b5
    # fused bank-set executor vs per-bank run_bank
    ex = kops.multibank_executor(spec5)
    assert ex.accepts_bankset
    fused = shift_rule.run_bank_set(ex, b5)
    plain = shift_rule.run_bank_set(kops.shiftbank_executor(spec5), b5)
    for f, p in zip(fused, plain):
        assert np.array_equal(np.asarray(f), np.asarray(p))


def test_worker_multibank_executor_matches_per_bank():
    """Fused multi-bank scheduling across workers: per-bank flat results
    match the materialized oracle for every bank in the set."""
    from repro.comanager import dataplane
    spec = circuits.build_quclassi_circuit(5, 2)
    banks = _banks(spec, 3)
    n_sub = sum(b.n_groups for b in banks)
    assignment = dataplane.round_robin_assignment(n_sub, 2)
    run = dataplane.worker_multibank_executor(spec, assignment, 2)
    assert run.accepts_bankset
    for bank, flat in zip(banks, run(banks)):
        mat = bank.materialize()
        want = kops.vqc_fidelity(spec, mat.theta, mat.data)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(want),
                                   atol=1e-5)


def test_worker_multibank_executor_validates():
    from repro.comanager import dataplane
    spec = circuits.build_quclassi_circuit(5, 1)
    banks = _banks(spec, 2)
    run = dataplane.worker_multibank_executor(spec, [0, 1], 2)
    with pytest.raises(ValueError, match="subtasks"):
        run(banks)


def test_sharded_executor_run_banks():
    """The mesh-sharded fused multi-bank path (the dispatcher's spill
    executor) agrees with the local fused kernel."""
    from repro.comanager import dataplane
    from repro.launch.mesh import make_host_mesh
    spec = circuits.build_quclassi_circuit(5, 2)
    banks = _banks(spec, 2)
    gs = tuple(tuple(range(b.n_groups)) for b in banks)
    args = (tuple(b.theta for b in banks), tuple(b.data for b in banks))
    run = dataplane.sharded_executor(spec, make_host_mesh())
    got = run.run_banks(*args, False, gs)
    want = kops.vqc_fidelity_shiftgroups_multibank(spec, *args, False, gs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


# ------------------------------------------- VMEM-aware checkpoint spilling
def test_wide_register_selects_spill_fast_path():
    """m = 8 at the production tile (TB = 512): the checkpoint set exceeds
    the VMEM budget and the planner selects depth-tiled spilling — the
    prefix-reuse fast path, NOT materialize()."""
    spec = circuits.build_quclassi_circuit(17, 3)     # m = 8
    assert K.build_shift_plan(spec) is not None       # fast path applies
    info = K.shift_execution_info(spec, 512)
    assert info["mode"] == "spill"
    assert info["n_tiles"] > 1
    assert info["launches"] == info["n_tiles"] + 1
    # the reported footprint includes the SECOND ping-pong boundary buffer
    # of the double-buffered backward launch (exactly one register state);
    # tiling itself still budgets without it — the nominal budget reserves
    # the double-buffering headroom below physical VMEM.
    assert info["spill_buffer_bytes"] == K._state_bytes(8, 512)
    assert info["vmem_bytes"] - info["spill_buffer_bytes"] <= info["vmem_budget"]
    assert 0 < info["overlap_ratio"] < 1
    # the paper's narrow registers stay on the single-sweep path
    narrow = K.shift_execution_info(circuits.build_quclassi_circuit(7, 3),
                                    512)
    assert narrow["mode"] == "fused" and narrow["launches"] == 1


def test_spilled_execution_matches_single_sweep_m8():
    """Numeric agreement of the spilled path on a genuinely wide register
    (m = 8, register-local states only — cheap): forced tiny budget vs the
    unconstrained single sweep."""
    spec = circuits.build_quclassi_circuit(17, 1)
    theta = jax.random.uniform(jax.random.PRNGKey(2), (spec.n_theta,),
                               jnp.float32, minval=0.0, maxval=np.pi)
    data = jax.random.uniform(jax.random.PRNGKey(3), (2, spec.n_data),
                              jnp.float32, minval=0.0, maxval=np.pi)
    bank = shift_rule.build_shift_bank(theta, data)
    plan = K.build_shift_plan(spec)
    budget = K.checkpoint_vmem_bytes(plan, 4, 128)    # fits ~4 checkpoints
    spilled = K.vqc_shift_fidelity(spec, bank.theta, bank.data,
                                   vmem_budget=budget)
    full = K.vqc_shift_fidelity(spec, bank.theta, bank.data)
    np.testing.assert_allclose(np.asarray(spilled), np.asarray(full),
                               atol=1e-5)


@pytest.mark.parametrize("four_term", [False, True])
def test_spilled_execution_matches_materialized(four_term):
    """Spill tiling vs the dense materialized oracle at a testable width."""
    spec = circuits.build_quclassi_circuit(7, 3)
    theta = jax.random.uniform(jax.random.PRNGKey(5), (spec.n_theta,),
                               jnp.float32, minval=0.0, maxval=np.pi)
    data = jax.random.uniform(jax.random.PRNGKey(6), (3, spec.n_data),
                              jnp.float32, minval=0.0, maxval=np.pi)
    bank = shift_rule.build_shift_bank(theta, data, four_term=four_term)
    plan = K.build_shift_plan(spec)
    budget = K.checkpoint_vmem_bytes(plan, 3, 128)
    tiles = K.plan_depth_tiles(plan, range(spec.n_theta), 128, budget)
    assert tiles is not None and len(tiles) > 1
    got = K.vqc_shift_fidelity(spec, bank.theta, bank.data,
                               four_term=four_term, vmem_budget=budget)
    mat = bank.materialize()
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data).reshape(
        bank.n_groups, bank.n_samples)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_spilled_group_subset():
    """Spilling composes with partial group requests (serving-path shape)."""
    spec = circuits.build_quclassi_circuit(5, 3)
    theta = jax.random.uniform(jax.random.PRNGKey(7), (spec.n_theta,),
                               jnp.float32, minval=0.0, maxval=np.pi)
    data = jax.random.uniform(jax.random.PRNGKey(8), (2, spec.n_data),
                              jnp.float32, minval=0.0, maxval=np.pi)
    groups = (0, 1, 4, 9, spec.n_theta * 2)
    plan = K.build_shift_plan(spec)
    budget = K.checkpoint_vmem_bytes(plan, 2, 128)
    got = K.vqc_shift_fidelity(spec, theta[None].repeat(2, 0), data,
                               groups=groups, vmem_budget=budget)
    want = K.vqc_shift_fidelity(spec, theta[None].repeat(2, 0), data,
                                groups=groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_plan_depth_tiles_boundaries():
    spec = circuits.build_quclassi_circuit(7, 3)
    plan = K.build_shift_plan(spec)
    assert K.plan_depth_tiles(plan, range(spec.n_theta), 128,
                              K.VMEM_BUDGET_BYTES) is None   # narrow: fits
    budget = K.checkpoint_vmem_bytes(plan, 3, 128)
    tiles = K.plan_depth_tiles(plan, range(spec.n_theta), 128, budget)
    # tiles partition [first_pos, n_train) contiguously, ascending
    assert tiles[0][0] == 0 and tiles[-1][1] == len(plan.train_ops)
    for (a, b), (c, d) in zip(tiles, tiles[1:]):
        assert b == c and a < b


def test_trainer_bank_mode_validation():
    from repro.core import trainer
    from repro.core.quclassi import QuClassiConfig
    with pytest.raises(ValueError, match="bank_mode"):
        trainer.train(QuClassiConfig(), (np.zeros((2, 8, 8)), np.zeros(2)),
                      (np.zeros((2, 8, 8)), np.zeros(2)),
                      epochs=0, bank_mode="bogus")


# ----------------------------------------- multi-use params: suffix replay
def _tied_setup(qc, nl, b=3, seed=0):
    spec = circuits.build_tied_quclassi_circuit(qc, nl)
    key = jax.random.PRNGKey(seed)
    theta = jax.random.uniform(key, (spec.n_theta,), jnp.float32,
                               minval=0.0, maxval=np.pi)
    data = jax.random.uniform(jax.random.fold_in(key, 1), (b, spec.n_data),
                              jnp.float32, minval=0.0, maxval=np.pi)
    return spec, theta, data


def _deep_reuse_spec(r=20):
    """One parameter driving ``r`` consecutive gates on a 1-qubit register:
    the replay span covers the whole trainable stack, so a single-variant
    request is analytically cheaper to materialize."""
    body = [Op("rx", (1,), ("data", 0))]
    body += [Op("ry", (2,), ("theta", 0)) for _ in range(r)]
    tail = [Op("h", (0,)), Op("cswap", (0, 1, 2)), Op("h", (0,))]
    return CircuitSpec(n_qubits=3, ops=tuple(body + tail), n_theta=1,
                       n_data=1)


def test_tied_circuit_plan_structure():
    """2-reuse ansatz: every parameter drives two adjacent gates; the plan
    records the full position tuple and the legacy view exposes firsts."""
    spec = circuits.build_tied_quclassi_circuit(7, 3)
    assert spec.n_theta == circuits.build_quclassi_circuit(7, 3).n_theta
    plan = K.build_shift_plan(spec)
    assert plan is not None
    assert len(plan.train_ops) == 2 * spec.n_theta
    for j, ps in enumerate(plan.theta_positions):
        assert ps == (2 * j, 2 * j + 1)
        assert plan.replay_depth(j) == 2
    assert plan.theta_pos == tuple(2 * j for j in range(spec.n_theta))


@pytest.mark.parametrize("qc,nl", [(5, 2), (7, 3)])
@pytest.mark.parametrize("four_term", [False, True])
def test_multiuse_fused_matches_materialized(qc, nl, four_term):
    """Suffix-replay fidelities agree with the materialize() oracle."""
    spec, theta, data = _tied_setup(qc, nl, b=3, seed=qc + nl)
    assert K.use_shift_plan(spec, four_term)   # implicit path selected
    bank = shift_rule.build_shift_bank(theta, data, four_term=four_term)
    mat = bank.materialize()
    got = kops.vqc_fidelity_shiftgroups(spec, bank.theta, bank.data,
                                        four_term)
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data).reshape(
        bank.n_groups, bank.n_samples)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_multiuse_spilled_matches_materialized():
    """Forced tiny budget: replay spans spill-tile without splitting."""
    spec, theta, data = _tied_setup(5, 3, b=3, seed=9)
    plan = K.build_shift_plan(spec)
    bank = shift_rule.build_shift_bank(theta, data)
    budget = K.checkpoint_vmem_bytes(plan, 3, 128)
    anchors = sorted(ps[-1] for ps in plan.theta_positions)
    tiles = K.plan_depth_tiles(plan, anchors, 128, budget)
    assert tiles is not None and len(tiles) > 1
    got = K.vqc_shift_fidelity(spec, bank.theta, bank.data,
                               vmem_budget=budget)
    mat = bank.materialize()
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data).reshape(
        bank.n_groups, bank.n_samples)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_multiuse_multibank_matches_per_bank():
    spec, theta, data = _tied_setup(5, 2, b=3, seed=4)
    theta2 = theta + 0.1
    b1 = shift_rule.build_shift_bank(theta, data)
    b2 = shift_rule.build_shift_bank(theta2, data)
    gs = (tuple(range(b1.n_groups)), (0, 1, 3))
    got = kops.vqc_fidelity_shiftgroups_multibank(
        spec, (b1.theta, b2.theta), (b1.data, b2.data), False, gs)
    want = tuple(
        kops.vqc_fidelity_shiftgroups(spec, b.theta, b.data, False, g)
        for b, g in zip((b1, b2), gs))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)


def test_plan_depth_tiles_never_split_replay_spans():
    """A multi-use parameter's [first, last] span is atomic under tiling:
    its checkpoint is always derivable inside its anchor's tile."""
    spec = circuits.build_tied_quclassi_circuit(7, 3)
    plan = K.build_shift_plan(spec)
    anchors = sorted(ps[-1] for ps in plan.theta_positions)
    budget = K.checkpoint_vmem_bytes(plan, 3, 128)
    tiles = K.plan_depth_tiles(plan, anchors, 128, budget)
    assert tiles is not None
    assert tiles[0][0] == 0 and tiles[-1][1] == len(plan.train_ops)
    for (a, b), (c, d) in zip(tiles, tiles[1:]):
        assert b == c and a < b
    for ps in plan.theta_positions:
        tile = next((lo, hi) for lo, hi in tiles if lo <= ps[-1] < hi)
        assert tile[0] <= ps[0], (ps, tile)   # first stays in anchor's tile


def test_cost_crossover_selects_materialize():
    """Plan selection is a cost comparison, not plan existence: one variant
    of a whole-circuit replay span is cheaper materialized, and the ops
    layer routes it there with unchanged numerics."""
    spec = _deep_reuse_spec(r=20)
    assert K.build_shift_plan(spec) is not None
    # full bank: implicit still wins (materializing pays data+tail per group)
    assert K.use_shift_plan(spec)
    full_cost = K.shift_cost_info(spec)
    assert full_cost["gate_apps_implicit"] < full_cost["gate_apps_materialized"]
    assert full_cost["replay_depth_max"] == 20
    # single deep variant: replay cost crosses over
    sub = K.shift_cost_info(spec, False, (1,))
    assert sub["gate_apps_implicit"] > sub["gate_apps_materialized"]
    assert not K.use_shift_plan(spec, False, (1,))
    info = K.shift_execution_info(spec, 8, groups=(1,))
    assert info["mode"] == "materialize"
    # the ops layer takes the materialized path and stays correct
    theta = jnp.asarray([[0.4], [1.1]], jnp.float32)
    data = jnp.asarray([[0.2], [0.8]], jnp.float32)
    bank = shift_rule.build_shift_bank(theta, data)
    got = kops.vqc_fidelity_shiftgroups(spec, bank.theta, bank.data, False,
                                        (1,))
    mat = bank.materialize()
    want = ref.vqc_fidelity_ref(spec, mat.theta, mat.data).reshape(
        bank.n_groups, 2)[1:2]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_cost_model_ranks_multiuse_banks():
    """Regression (CostModel mis-ranking): a 2-reuse bank is charged the
    analytic suffix-replay cost, NOT the full materialized cost — so the
    coalescer/placement rank it between the single-use bank and the
    materialized fallback."""
    from repro.api.backend import CostModel
    cm = CostModel(shiftbank=True)
    qc, nl = 7, 3
    single = circuits.build_quclassi_circuit(qc, nl)
    tied = circuits.build_tied_quclassi_circuit(qc, nl)
    theta = jnp.zeros((single.n_theta,), jnp.float32)
    data = jnp.zeros((64, single.n_data), jnp.float32)
    bank_s = shift_rule.build_shift_bank(theta, data)
    bank_t = shift_rule.build_shift_bank(theta, data)
    cost_single = cm.bank_cost_units(single, bank_s)
    cost_tied = cm.bank_cost_units(tied, bank_t)
    mat_tied = cm.bank_cost_units(tied, bank_t.materialize())
    # pinned ordering: single-use < 2-reuse replay << materialized
    assert cost_single < cost_tied < mat_tied
    assert cost_tied <= mat_tied / 3      # the >=3x acceptance headroom
    # the charge IS the analytic replay cost
    want = K.shift_cost_info(tied)["gate_apps_implicit"] * 128
    assert cost_tied == float(want)
    # deep-reuse full-span banks still never exceed the materialized charge
    # (at lane-saturating batch sizes where padding doesn't skew the units)
    deep = _deep_reuse_spec(r=20)
    bank_d = shift_rule.build_shift_bank(
        jnp.zeros((128, 1), jnp.float32), jnp.zeros((128, 1), jnp.float32))
    assert cm.bank_cost_units(deep, bank_d) < cm.bank_cost_units(
        deep, bank_d.materialize())


def test_shift_bank_stats_multiuse_ratio():
    """The 7q/3l 2-reuse ansatz clears the >=3x gate-apps acceptance bar."""
    spec = circuits.build_tied_quclassi_circuit(7, 3)
    stats = K.shift_bank_stats(spec, 64)
    assert stats["gate_apps_ratio"] >= 3.0, stats
    # and the classic single-use ratio is unchanged by the generalization
    classic = K.shift_bank_stats(circuits.build_quclassi_circuit(7, 3), 64)
    assert classic["gate_apps_ratio"] >= 5.0, classic
