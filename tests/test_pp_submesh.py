"""Host-side unit tests for the measured submesh pipeline (core/pp_submesh,
DESIGN.md §2.8): the stage-stacking geometry and its zero-pad invariants, the
hand-off byte ledger arithmetic, and the staged-mesh validation errors. The
live 16-device execution path is tests/dist/session_submesh_pp.py (run by
test_runtime.test_session_submesh_pp_measured)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import nonuniform as nu
from repro.core import ntp_train as nt
from repro.core import pp_submesh
from repro.core.nonuniform import FailurePlan
from repro.launch.mesh import make_staged_mesh


def _cfg(n_layers=3):
    return nt.NTPModelConfig(d_model=32, n_kv_groups=2, q_per_kv=1,
                             head_dim=16, d_ff=64, unit_rows=32,
                             n_layers=n_layers, vocab=64)


def _staged():
    # stage 1 degraded (replica 1 at tp=1): its unit buffers are WIDER than
    # stage 0's, so stacking must pad stage 0; n_layers=3 over pp=2 gives
    # stages of 2 and 1 layers, so stage 1 also pads a whole layer.
    return nu.StagedPlan((FailurePlan(n1=2, replica_tp=(2, 2)),
                          FailurePlan(n1=2, replica_tp=(2, 1))))


# ---------------------------------------------------------------------------
# stage stacking: geometry, specs, content, pad invariants

def test_stack_staged_params_geometry_and_content():
    cfg = _cfg()
    staged = _staged()
    canon = nt.init_canonical(cfg, jax.random.PRNGKey(0))
    packed = nt.pack_params(cfg, canon, staged)
    stacked, specs = pp_submesh.stack_staged_params(cfg, packed, staged)

    stage_layers, l_max, plans, u_max = pp_submesh._stage_geometry(cfg, staged)
    assert stage_layers == [(0, 1), (2,)] and l_max == 2
    # the degraded stage's redistribution widens the per-rank unit buffer
    assert u_max["attn"] == 2 and u_max["mlp"] == 2

    # global (non-layer) leaves pass through untouched and replicated
    for k in ("embed", "head", "final_norm"):
        assert stacked[k] is packed[k] and specs[k] == P()

    d, n1 = staged.d, staged.n1
    for key in nt.UNIT_KEYS:
        kind = "attn" if key in pp_submesh._ATTN_KEYS else "mlp"
        leaf = np.asarray(stacked["unit"][key])
        assert specs["unit"][key] == P("stage", None, "data", "model")
        unit_tail = np.asarray(packed["layers"][0][key]).shape[2:]
        assert leaf.shape == (2, l_max, d, n1 * u_max[kind], *unit_tail)
        for s, layers in enumerate(stage_layers):
            u_s = plans[s][kind].comp_slots.shape[2]
            for l, li in enumerate(layers):
                row = leaf[s, l].reshape(d, n1, u_max[kind], *unit_tail)
                want = np.asarray(packed["layers"][li][key]).reshape(
                    d, n1, u_s, *unit_tail)
                # real slots are the packed layer verbatim...
                assert np.array_equal(row[:, :, :u_s], want), (key, s, l)
                # ...and pad slots are exactly zero (algebraically inert)
                assert not row[:, :, u_s:].any(), (key, s, l)
            # stages owning fewer layers pad with all-zero layers
            for l in range(len(layers), l_max):
                assert not leaf[s, l].any(), (key, s, l)

    for key in ("ln1", "ln2"):
        leaf = np.asarray(stacked["rep"][key])
        # replicated on purpose: sharding these P("stage") tripped a
        # partitioner bug on jax 0.4.x when the stack was traced into the
        # step's jit (not re-checked on the installed jax 0.9)
        assert specs["rep"][key] == P()
        assert leaf.shape[:2] == (2, l_max)
        for s, layers in enumerate(stage_layers):
            for l, li in enumerate(layers):
                assert np.array_equal(
                    leaf[s, l], np.asarray(packed["layers"][li][key]))
            for l in range(len(layers), l_max):
                assert not leaf[s, l].any()


def test_stack_staged_params_no_pad_when_uniform():
    """Healthy plan, layers dividing evenly: stacking is pure reshape — every
    slot is a real weight, nothing padded."""
    cfg = _cfg(n_layers=4)
    staged = nu.StagedPlan((FailurePlan(n1=2, replica_tp=(2, 2)),) * 2)
    packed = nt.pack_params(cfg, nt.init_canonical(cfg, jax.random.PRNGKey(1)),
                            staged)
    stacked, _ = pp_submesh.stack_staged_params(cfg, packed, staged)
    for key in nt.UNIT_KEYS:
        leaf = np.asarray(stacked["unit"][key])
        assert leaf.shape[:2] == (2, 2)
        for s, layers in enumerate(((0, 1), (2, 3))):
            for l, li in enumerate(layers):
                assert np.array_equal(
                    leaf[s, l], np.asarray(packed["layers"][li][key]))


# ---------------------------------------------------------------------------
# hand-off ledger arithmetic

def test_handoff_accounting_table():
    cfg = _cfg()
    staged = _staged()
    t = pp_submesh.handoff_accounting(cfg, staged, local_batch=8,
                                      microbatches=4, seq_len=16)
    mb = 8 // 4
    assert t["ticks"] == 4 + 2 - 1
    assert t["act_bytes_per_send"] == 4 * mb * 16 * cfg.d_model
    assert t["sender_ranks"] == (2 - 1) * staged.d * staged.n1
    assert t["sends_per_boundary"] == t["ticks"] - 1
    assert t["fwd_bytes"] == (t["act_bytes_per_send"] * t["sender_ranks"]
                              * t["sends_per_boundary"])
    assert t["bwd_bytes"] == t["fwd_bytes"]            # ppermute transpose
    assert t["total_bytes"] == 2 * t["fwd_bytes"]


def test_handoff_accounting_scales_with_stages():
    """One boundary per extra stage; more microbatches -> smaller sends but
    more of them, total forward volume m*(pp-1)+... per the tick schedule."""
    cfg = _cfg(n_layers=8)
    p2 = nu.StagedPlan((FailurePlan(n1=2, replica_tp=(2, 2)),) * 2)
    p4 = nu.StagedPlan((FailurePlan(n1=2, replica_tp=(2, 2)),) * 4)
    t2 = pp_submesh.handoff_accounting(cfg, p2, local_batch=8,
                                       microbatches=2, seq_len=16)
    t4 = pp_submesh.handoff_accounting(cfg, p4, local_batch=8,
                                       microbatches=2, seq_len=16)
    assert t4["sender_ranks"] == 3 * t2["sender_ranks"]
    assert t4["ticks"] == 2 + 4 - 1
    assert t4["sends_per_boundary"] == t4["ticks"] - 1


# ---------------------------------------------------------------------------
# mesh predicates + validation errors

class _StubMesh:
    def __init__(self, names, shape):
        self.axis_names = tuple(names)
        self.shape = dict(shape)


def test_is_staged_mesh():
    assert not pp_submesh.is_staged_mesh(None)
    assert not pp_submesh.is_staged_mesh(
        _StubMesh(("data", "model"), {"data": 2, "model": 4}))
    assert not pp_submesh.is_staged_mesh(
        _StubMesh(pp_submesh.STAGE_AXES, {"stage": 1, "data": 2, "model": 4}))
    assert pp_submesh.is_staged_mesh(
        _StubMesh(pp_submesh.STAGE_AXES, {"stage": 2, "data": 2, "model": 4}))


def test_validate_staged_mesh_errors():
    good = _StubMesh(pp_submesh.STAGE_AXES,
                     {"stage": 2, "data": 2, "model": 4})
    pp_submesh.validate_staged_mesh(good, 2)   # no raise
    with pytest.raises(ValueError, match="make_staged_mesh"):
        pp_submesh.validate_staged_mesh(
            _StubMesh(("data", "model"), {"data": 2, "model": 4}), 2)
    with pytest.raises(ValueError, match="one submesh per pipeline stage"):
        pp_submesh.validate_staged_mesh(good, 3)


def test_make_staged_mesh_errors():
    with pytest.raises(ValueError, match="make_test_mesh"):
        make_staged_mesh(1, 2, 4)
    # geometry no host can satisfy -> the error counts the shortfall
    with pytest.raises(ValueError, match=r"needs 32768 devices"):
        make_staged_mesh(2, 128, 128)


def test_make_submesh_train_step_validates_microbatching():
    cfg = _cfg(n_layers=4)
    staged = nu.StagedPlan((FailurePlan(n1=2, replica_tp=(2, 2)),) * 2)
    mesh = _StubMesh(pp_submesh.STAGE_AXES,
                     {"stage": 2, "data": 2, "model": 2})
    with pytest.raises(ValueError, match="microbatches=0 outside"):
        pp_submesh.make_submesh_train_step(cfg, staged, mesh, local_batch=4,
                                           microbatches=0)
    with pytest.raises(ValueError, match="not divisible by"):
        pp_submesh.make_submesh_train_step(cfg, staged, mesh, local_batch=4,
                                           microbatches=3)
