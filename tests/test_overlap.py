"""`core.overlap` host-side (ISSUE 9): bucket geometry, the Pallas
pack/unpack kernels, the bucketed-vs-sequential sync property against the
numpy reshard twin, and the overlap-aware perf-model entry points.

The live multi-device overlapped step (AD inside shard_map, chunked
backward, in-flight buckets) is covered by tests/dist/session_overlap_pp.py
and tests/dist/session_overlap_submesh_pp.py; everything here runs on one
host device.

The central property: WeightPlan reshard tables index unit ROWS only, so
column-concatenating leaves that share a (stage, plan) commutes with the
gather/scatter and the elementwise psum — the bucketed sync must equal the
sequential per-leaf sync EXACTLY, healthy or degraded, across arbitrary
fail/repair chains. A deterministic sweep always runs; the hypothesis
version widens the search when the dev dependency is installed.
"""
import numpy as np
import pytest

from repro.core import nonuniform as nu
from repro.core import perf_model as pm
from repro.core.nonuniform import FailurePlan
from repro.core.overlap import (
    Bucket, bucket_layout, chunk_ranges, coerce_overlap, sync_collectives,
)
from repro.kernels import ops
from repro.kernels.bucket import bucket_pack_ref, bucket_unpack_ref
from repro.runtime import NTPModelConfig

from test_reshard_properties import _rank_buffers, emulate_reshard

CFG4 = NTPModelConfig(d_model=64, n_kv_groups=4, q_per_kv=2, head_dim=16,
                      d_ff=256, unit_rows=64, n_layers=4, vocab=128)


# --------------------------------------------------------------- geometry


def test_coerce_overlap():
    assert coerce_overlap(True) and not coerce_overlap(False)
    assert coerce_overlap("on") and coerce_overlap("true")
    assert not coerce_overlap("off") and not coerce_overlap("0")
    with pytest.raises(ValueError):
        coerce_overlap("sometimes")


@pytest.mark.parametrize("n_layers,pp,want", [
    (4, 1, ((0, 1), (1, 2), (2, 3), (3, 4))),   # pp=1: DEFAULT_CHUNKS ladder
    (4, 2, ((0, 2), (2, 4))),                   # pp>1: the stage boundaries
    (2, 1, ((0, 1), (1, 2))),                   # fewer layers than chunks
    (6, 2, ((0, 3), (3, 6))),
])
def test_chunk_ranges(n_layers, pp, want):
    got = chunk_ranges(n_layers, pp)
    assert got == want
    # always a contiguous, non-empty cover of [0, n_layers)
    assert got[0][0] == 0 and got[-1][1] == n_layers
    assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
    assert all(hi > lo for lo, hi in got)


def test_bucket_layout_reversed_and_stage_pure():
    staged = nu.StagedPlan((FailurePlan(4, (4, 4)), FailurePlan(4, (3, 4))))
    layout = bucket_layout(CFG4, staged)
    # reversed chunk order: the backward reaches the LAST stage's grads first
    assert [b.stage for b in layout] == [1, 1, 0, 0]
    assert [b.kind for b in layout] == ["attn", "mlp", "attn", "mlp"]
    attn1 = layout[0]
    assert attn1 == Bucket(1, "attn", ((2, "wq"), (2, "wk"), (2, "wv"),
                                       (2, "wo"), (3, "wq"), (3, "wk"),
                                       (3, "wv"), (3, "wo")))
    # a chunk straddling stages is a geometry bug, not a silent merge
    with pytest.raises(AssertionError):
        bucket_layout(CFG4, staged, chunks=((0, 3), (3, 4)))


def test_sync_collectives_collapse():
    chunks = chunk_ranges(CFG4.n_layers, 1)
    healthy = FailurePlan(4, (4, 4))
    degraded = FailurePlan(4, (3, 4))
    # sequential: one launch per unit leaf (6/layer), x3 when degraded
    assert sync_collectives(CFG4, healthy, "ntp", bucketed=False) == 24
    assert sync_collectives(CFG4, degraded, "ntp", bucketed=False) == 72
    # bucketed on the pp=1 ladder: one launch per (chunk, kind)
    assert sync_collectives(CFG4, healthy, "ntp", bucketed=True,
                            chunks=chunks) == 8
    assert sync_collectives(CFG4, degraded, "ntp", bucketed=True,
                            chunks=chunks) == 24
    # uniform mode never reshards, even on a degraded-shaped plan
    assert sync_collectives(CFG4, degraded, "uniform", bucketed=False) == 24
    # staged: only the degraded STAGE pays the x3
    staged = nu.StagedPlan((FailurePlan(4, (4, 4)), FailurePlan(4, (2, 4))))
    assert sync_collectives(CFG4, staged, "ntp", bucketed=False) \
        == 12 * 1 + 12 * 3
    assert sync_collectives(CFG4, staged, "ntp", bucketed=True) \
        == 2 * 1 + 2 * 3


# --------------------------------------------------- pack/unpack kernels


def _leaves(rng, rows, widths):
    return [rng.standard_normal((rows, w)).astype(np.float32)
            for w in widths]


@pytest.mark.parametrize("widths", [(3,), (1, 1), (4, 2, 7), (8, 8, 8, 8),
                                    # 128-aligned: column-tile grids of
                                    # 6 and 3 tiles
                                    (256, 384, 128), (1024, 2048)])
def test_bucket_pack_unpack_matches_ref(widths):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    leaves = [jnp.asarray(a) for a in _leaves(rng, 16, widths)]
    flat = ops.bucket_pack(leaves, interpret=True)
    ref = bucket_pack_ref(leaves)
    assert flat.shape == (16, sum(widths))
    assert np.array_equal(np.asarray(flat), np.asarray(ref))
    parts = ops.bucket_unpack(flat, widths, interpret=True)
    ref_parts = bucket_unpack_ref(flat, widths)
    for p, rp, leaf in zip(parts, ref_parts, leaves):
        assert np.array_equal(np.asarray(p), np.asarray(leaf))
        assert np.array_equal(np.asarray(p), np.asarray(rp))


def test_bucket_pack_validates():
    import jax.numpy as jnp

    a = jnp.zeros((8, 3), jnp.float32)
    with pytest.raises(ValueError):
        ops.bucket_pack([a, jnp.zeros((4, 3), jnp.float32)],
                        interpret=True)  # row mismatch
    with pytest.raises(ValueError):
        ops.bucket_pack([a, jnp.zeros((8, 3), jnp.bfloat16)],
                        interpret=True)  # dtype mismatch
    with pytest.raises(ValueError):
        ops.bucket_pack([jnp.zeros((8,), jnp.float32)],
                        interpret=True)  # not 2-D


# ----------------------------------- bucketed == sequential sync property


def _ntp_sync(wp, bufs):
    """Numpy twin of the full Algorithm-1 sync: per-replica pre-reshard,
    psum('data'), per-replica post-reshard. bufs: (D, n1, buf, cols)."""
    d = bufs.shape[0]
    pre = np.stack([emulate_reshard(bufs[r], wp.pre, r) for r in range(d)])
    summed = pre.sum(axis=0)
    return np.stack([emulate_reshard(summed, wp.post, r) for r in range(d)])


def _check_bucketed_equals_sequential(plan, k, widths, seed):
    wp = nu.weight_plan(k, plan)
    rng = np.random.default_rng(seed)
    # independent per-replica gradients, one canonical (k, w) leaf each
    leaves = [[rng.standard_normal((k, w)).astype(np.float32)
               for w in widths] for _ in range(plan.d)]
    bufs = [np.stack([_rank_buffers(wp, leaves[r][i], 1)[r]
                      for r in range(plan.d)])
            for i in range(len(widths))]          # per-leaf (D, n1, buf, w)

    seq = [_ntp_sync(wp, b) for b in bufs]
    fused = _ntp_sync(wp, np.concatenate(bufs, axis=3))
    offs = np.cumsum((0,) + widths)
    for i in range(len(widths)):
        got = fused[..., offs[i]:offs[i + 1]]
        assert np.array_equal(got, seq[i]), (plan, k, widths, i)


def _random_chain(rng, events=4):
    """A fail/repair chain of plan states starting pristine."""
    n1, d = 4, int(rng.integers(2, 4))
    tp = [n1] * d
    chain = [FailurePlan(n1=n1, replica_tp=tuple(tp))]
    for _ in range(events):
        r = int(rng.integers(0, d))
        if tp[r] > 1 and rng.random() < 0.6:
            tp[r] -= 1                            # GPU failure
        else:
            tp[r] = n1                            # repair to pristine
        chain.append(FailurePlan(n1=n1, replica_tp=tuple(tp)))
    return chain


@pytest.mark.parametrize("seed", range(4))
def test_bucketed_equals_sequential_over_chain(seed):
    """Deterministic sweep: every plan state of a random fail/repair chain,
    bucketed == sequential bit-for-bit (the twin's sums are in the same
    order, so even degraded states compare exactly)."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(4, 12))
    widths = tuple(int(w) for w in rng.integers(1, 6, size=3))
    for plan in _random_chain(rng):
        _check_bucketed_equals_sequential(plan, k, widths, seed)


def test_bucketed_equals_sequential_hypothesis():
    """Property-based widening of the chain sweep (dev dependency)."""
    pytest.importorskip("hypothesis",
                        reason="dev dependency: pip install -e .[dev]")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           k=st.integers(4, 16),
           widths=st.lists(st.integers(1, 8), min_size=1, max_size=4))
    def prop(seed, k, widths):
        rng = np.random.default_rng(seed)
        for plan in _random_chain(rng, events=3):
            _check_bucketed_equals_sequential(plan, k, tuple(widths), seed)

    prop()


# ------------------------------------------------- perf-model overlap API


def test_exposed_comm_identity():
    assert pm.exposed_comm(3.0, 1.0) == 2.0
    assert pm.exposed_comm(1.0, 2.0) == 0.0
    assert pm.exposed_comm(0.0, 0.0) == 0.0


def test_overlap_iteration_time_decomposition():
    hw, wl, par = pm.Hardware(), pm.Workload(), pm.Parallel()
    o0 = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=0.0)
    # zero window: the whole sync is exposed and total decomposes exactly
    assert o0["exposed_comm"] == pytest.approx(o0["sync"])
    assert o0["total"] == pytest.approx(
        o0["compute"] + o0["tp_exposed"] + o0["pp_bubble"] + o0["sync"])
    o7 = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=0.7)
    assert o7["total"] <= o0["total"]
    assert o7["exposed_comm"] == pm.exposed_comm(o7["sync"],
                                                 o7["overlap_window"])
    # a big enough window hides the sync entirely
    o1 = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=1.0)
    if o1["overlap_window"] >= o1["sync"]:
        assert o1["exposed_comm"] == 0.0
        assert o1["total"] == pytest.approx(
            o1["compute"] + o1["tp_exposed"] + o1["pp_bubble"])


def test_overlap_iteration_time_collective_ratio_and_degraded():
    hw, wl, par = pm.Hardware(), pm.Workload(), pm.Parallel()
    a = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=0.0)
    b = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=0.0,
                                  collective_ratio=2.0)
    assert b["sync"] == pytest.approx(2.0 * a["sync"])
    # degraded replica: the reshard chain joins the sync term in full
    d = pm.overlap_iteration_time(hw, wl, par, overlappable_fraction=0.0,
                                  tp_reduced=par.tp // 2)
    assert d["reshard_exposed"] > 0
    assert d["sync"] == pytest.approx(d["dp_exposed"] + d["reshard_exposed"])


def test_iteration_time_reshard_overlap_knob_keeps_legacy_default():
    hw, wl, par = pm.Hardware(), pm.Workload(), pm.Parallel()
    kw = dict(tp_reduced=par.tp // 2)
    legacy = pm.iteration_time(hw, wl, par, **kw)
    full = pm.iteration_time(hw, wl, par, reshard_overlap=0.0, **kw)
    hidden = pm.iteration_time(hw, wl, par, reshard_overlap=1.0, **kw)
    # None keeps the Fig.-8 10%-exposed heuristic exactly
    assert legacy["reshard_exposed"] == pytest.approx(
        0.1 * full["reshard_exposed"])
    assert hidden["reshard_exposed"] == 0.0
