"""Elastic rounds: partial-participation sync + round-boundary membership.

The contract under test (core/sync.py §Partial participation,
core/engine.py §sync="partial" / MembershipEpoch):

  * `make_sync_partial` with an all-ones mask is BITWISE the blocking sync
    for power-of-two W, on every layout — the partial path is the blocking
    path with a mask, not a reimplementation;
  * a masked (quantized) sync equals a W'=|P| run over just the participant
    rows, bitwise — Σ_{i∈P} q_i / |P| is the same integer sum whether the
    absent lanes contribute zero codes or don't exist.  |P|=3 is deliberate:
    non-power-of-two divisors are where f32 mean-vs-division tricks break,
    and the integer-code domain doesn't care;
  * the exact apply broadcasts consensus to ALL W lanes — a masked lane
    re-anchors at the same boundary (the rejoin rule);
  * `membership_epoch()` is the only legal mutation point for the worker
    set: masks change without recompiling (traced argument), resizes re-pad
    the W axis through the tree layout and park — not evict — the old-W
    compile-cache entries, and every change appends a MembershipEpoch;
  * `restore_elastic` accepts a checkpoint written under ANY worker count:
    surviving lanes restore bitwise, joining lanes clone lane 0 (params AND
    moments — the consensus replica a rejoining worker re-anchors to).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry as R
from repro.configs.base import RunConfig
from repro.core import engine as E
from repro.core import flat as F
from repro.core import schedules
from repro.core.sync import make_sync, make_sync_begin, make_sync_partial
from repro.launch.mesh import make_mesh
from repro.optim.lr import make_lr_fn


# ------------------------------------------------ sync-level (no engine) --

def _demo_params(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {"w_in": mk(13, 24), "bias": mk(17), "gate": mk(3, 5, 7),
            "h_bf16": mk(9, 11).astype(jnp.bfloat16)}


def _flat_state(spec, params, w, quantize, momentum):
    stacked = {k: jnp.broadcast_to(v[None], (w,) + v.shape)
               for k, v in params.items()}
    st = {"params": spec.flatten(stacked, lead=1)}
    if quantize or momentum > 0.0:
        st["anchor"] = spec.flatten(params)
    if momentum > 0.0:
        st["outer_mu"] = {b: jnp.zeros(spec.buffer_size(b), jnp.float32)
                          for b in spec.buckets}
    return st


def _perturb(st, spec, noise):
    nb = spec.flatten({k: jnp.asarray(v) for k, v in noise.items()}, lead=1)
    return dict(st, params={b: st["params"][b] + nb[b].astype(
        st["params"][b].dtype) for b in st["params"]})


@pytest.mark.parametrize("quantize,momentum", [
    (False, 0.0), (True, 0.0), (True, 0.9),
])
def test_partial_all_ones_bitwise_blocking_sync(quantize, momentum):
    """All-ones partial == blocking, bitwise, for power-of-two W (Σ/W as
    true IEEE division matches jnp.mean's reciprocal multiply exactly iff
    the divisor is a power of two)."""
    w, rounds = 4, 3
    params = _demo_params()
    run_cfg = RunConfig(sync_quantize=quantize, outer_momentum=momentum)
    spec = F.ShardedFlatSpace(params, w)
    part = jax.jit(make_sync_partial(run_cfg, spec))
    # blocking reference through the composed halves (the fused flat kernel
    # is proven equal to them in tests/test_flat.py)
    begin = jax.jit(make_sync_begin(run_cfg, spec))
    from repro.core.sync import make_sync_apply
    apply_ = jax.jit(make_sync_apply(run_cfg, spec))
    ones = jnp.ones(w, jnp.float32)
    sa = sb = _flat_state(spec, params, w, quantize, momentum)
    rng = np.random.RandomState(1)
    for _ in range(rounds):
        noise = {k: (rng.randn(w, *v.shape) * 0.01).astype(np.float32)
                 for k, v in params.items()}
        sa = part(_perturb(sa, spec, noise), ones)
        st = _perturb(sb, spec, noise)
        sb = apply_(st, begin(st))
    for k in sa:
        for b in sa[k]:
            np.testing.assert_array_equal(np.asarray(sa[k][b]),
                                          np.asarray(sb[k][b]))


def test_partial_masked_quantized_equals_participant_run():
    """The elastic exactness claim: mask [1,1,0,1] over W=4 produces
    bitwise the consensus of a 3-worker run over the participant rows
    (|P|=3 — a NON-power-of-two divisor; exact because the mean runs in
    the integer-code domain), and the masked lane re-anchors to it."""
    w, rows, rounds = 4, [0, 1, 3], 3
    params = _demo_params()
    run_cfg = RunConfig(sync_quantize=True)
    spec4 = F.ShardedFlatSpace(params, w)
    spec3 = F.ShardedFlatSpace(params, len(rows))
    part4 = jax.jit(make_sync_partial(run_cfg, spec4))
    part3 = jax.jit(make_sync_partial(run_cfg, spec3))
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    ones = jnp.ones(len(rows), jnp.float32)
    s4 = _flat_state(spec4, params, w, True, 0.0)
    s3 = _flat_state(spec3, params, len(rows), True, 0.0)
    rng = np.random.RandomState(2)
    for _ in range(rounds):
        noise = {k: (rng.randn(w, *v.shape) * 0.01).astype(np.float32)
                 for k, v in params.items()}
        s4 = part4(_perturb(s4, spec4, noise), mask)
        s3 = part3(_perturb(
            s3, spec3, {k: v[rows] for k, v in noise.items()}), ones)
    full = spec4.unflatten(s4["params"], lead=1)
    part = spec3.unflatten(s3["params"], lead=1)
    for k in full:
        # consensus over participants == the |P|-run's consensus, bitwise
        np.testing.assert_array_equal(np.asarray(full[k][0]),
                                      np.asarray(part[k][0]))
        # the masked lane was broadcast the same consensus: re-anchored
        np.testing.assert_array_equal(np.asarray(full[k][2]),
                                      np.asarray(full[k][0]))


def test_partial_scales_come_from_participants_only():
    """An absent lane with a huge delta must not inflate the quantization
    scales: its delta is zeroed BEFORE the amax statistic."""
    params = {"w": jnp.zeros((8, 8), jnp.float32)}
    run_cfg = RunConfig(sync_quantize=True)
    spec = F.ShardedFlatSpace(params, 2)
    st = _flat_state(spec, params, 2, True, 0.0)
    # lane 1 (masked) runs away; lane 0 moves by exactly 0.5 everywhere
    noise = {"w": np.stack([np.full((8, 8), 0.5, np.float32),
                            np.full((8, 8), 1e6, np.float32)])}
    out = make_sync_partial(run_cfg, spec)(
        _perturb(st, spec, noise), jnp.asarray([1.0, 0.0]))
    got = spec.unflatten(out["params"], lead=1)["w"]
    # participant amax = 0.5 -> codes ±127 exact -> consensus == +0.5.
    # had lane 1 leaked into the scale (1e6), 0.5 would quantize to 0.
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.full((8, 8), 0.5, np.float32))


def test_partial_does_not_compose_with_ring_wire():
    run_cfg = RunConfig(sync_quantize=True, sync_wire="ring-int8")
    spec = F.ShardedFlatSpace(_demo_params(), 4)
    with pytest.raises(ValueError, match="partial"):
        make_sync_begin(run_cfg, spec, partial=True)


# ------------------------------------------------------- engine level -----

def _mk_engine(sync="partial", layout="flat_sharded", workers=4, steps=8,
               quantize=True, momentum=0.0, **kw):
    cfg = R.get_smoke_config("starcoder2-3b")
    run = RunConfig(schedule="constant", optimizer="adamw",
                    total_steps=steps, peak_lr=3e-3, warmup_steps=1,
                    h_base=2, remat=False, weight_decay=0.01,
                    sync_quantize=quantize, outer_momentum=momentum)
    eng = E.RoundEngine(cfg, run, workers=workers, b_loc=2, seq=16,
                        data="device", layout=layout, sync=sync, **kw)
    return eng, make_lr_fn(run)


@pytest.mark.parametrize("layout", ["tree", "flat", "flat_sharded"])
def test_engine_partial_all_ones_bitwise_blocking(layout):
    """A sync="partial" engine with default (all-ones) membership runs
    bitwise the blocking engine — same programs, same rounds, W=4."""
    ep, lr_fn = _mk_engine(sync="partial", layout=layout)
    eb, _ = _mk_engine(sync="blocking", layout=layout)
    sp, sb = ep.init_state(), eb.init_state()
    for t in (0, 2, 4):
        sp, mp = ep.run_round(sp, t, 2, lr_fn)
        sb, mb = eb.run_round(sb, t, 2, lr_fn)
        assert float(mp["loss"]) == float(mb["loss"])
    la, lb = jax.tree.leaves(sp), jax.tree.leaves(sb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_membership_mask_changes_without_recompile():
    """A membership change is a traced argument: masking lane 2 out and
    back in reuses the same (Hp, W) program — zero new compiles — and
    every change lands in the epoch audit trail."""
    eng, lr_fn = _mk_engine()
    st = eng.init_state()
    st, _ = eng.run_round(st, 0, 2, lr_fn)
    n = eng.compiles
    eng.membership_epoch([1, 1, 0, 1])
    st, _ = eng.run_round(st, 2, 2, lr_fn)
    eng.membership_epoch([1, 1, 1, 1])
    st, _ = eng.run_round(st, 4, 2, lr_fn)
    assert eng.compiles == n, "mask changes must not recompile"
    assert [e.membership for e in eng.epochs] == [
        (1.0, 1.0, 0.0, 1.0), (1.0, 1.0, 1.0, 1.0)]
    assert not any(e.resized for e in eng.epochs)


def test_engine_masked_lane_reanchors_to_consensus():
    """After a partial round, the masked lane's params equal lane 0's (the
    consensus broadcast) — the rejoin rule at the state level."""
    eng, lr_fn = _mk_engine(layout="tree")
    st = eng.init_state()
    eng.membership_epoch([1, 1, 0, 1])
    st, _ = eng.run_round(st, 0, 2, lr_fn)
    for leaf in jax.tree.leaves(st["params"]):
        np.testing.assert_array_equal(np.asarray(leaf[2]),
                                      np.asarray(leaf[0]))


def test_membership_epoch_guards():
    eng, lr_fn = _mk_engine()
    st = eng.init_state()
    with pytest.raises(E.MembershipError, match="at least one participant"):
        eng.membership_epoch([0, 0, 0, 0])
    with pytest.raises(E.MembershipError, match="must be"):
        eng.membership_epoch([1, 1, 1])
    with pytest.raises(E.MembershipError, match="needs the run state"):
        eng.membership_epoch(keep_lanes=(0, 1))
    with pytest.raises(E.MembershipError, match="out of range"):
        eng.membership_epoch(state=st, keep_lanes=(0, 9))
    with pytest.raises(E.MembershipError, match="does not grow"):
        eng.membership_epoch(state=st, grow_to=4)
    # a pending overlap sync blocks ANY membership change
    eo, lr_fn = _mk_engine(sync="overlap", mode="bucketed")
    so = eo.init_state()
    so, _ = eo.run_round(so, 0, 2, lr_fn)
    with pytest.raises(E.MembershipError, match="round boundary"):
        eo.membership_epoch([1, 1, 0, 1])


def test_membership_resize_refused_under_mesh():
    """Mesh-backed engines resize via checkpoint + respawn, never in place
    (jax.distributed cannot shrink a live process group)."""
    jmesh = make_mesh((1, 1), ("data", "model"))
    eng, _ = _mk_engine(workers=1, mesh=jmesh, policy="dp")
    st = eng.init_state()
    with pytest.raises(E.MembershipError, match="respawn"):
        eng.membership_epoch(state=st, keep_lanes=(0,))


def test_engine_resize_shrink_then_grow_clones_consensus():
    """keep_lanes shrinks the W axis (kept lanes bitwise); grow_to clones
    lane 0's params AND moments into the joined lane; the old-W compile
    cache entries are parked, not evicted, and the epoch trail records
    both resizes."""
    eng, lr_fn = _mk_engine(workers=4)
    st = eng.init_state()
    st, _ = eng.run_round(st, 0, 2, lr_fn)
    before = jax.tree.map(np.asarray, F.to_tree_state(eng.spec, st))
    st = eng.membership_epoch(state=st, keep_lanes=(0, 1, 3))
    assert eng.workers == 3
    shrunk = F.to_tree_state(eng.spec, st)
    la = jax.tree.leaves(before["params"])
    lb = jax.tree.leaves(shrunk["params"])
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a[[0, 1, 3]], np.asarray(b))
    st, _ = eng.run_round(st, 2, 2, lr_fn)          # runs at W=3
    assert (2, 3) in eng._programs and (2, 4) in eng._programs
    st = eng.membership_epoch(state=st, grow_to=4)
    assert eng.workers == 4
    grown = F.to_tree_state(eng.spec, st)
    for leaf in jax.tree.leaves(grown["params"]):
        np.testing.assert_array_equal(np.asarray(leaf[3]),
                                      np.asarray(leaf[0]))
    for k in ("m", "v"):
        for leaf in jax.tree.leaves(grown["opt"][k]):
            np.testing.assert_array_equal(np.asarray(leaf[3]),
                                          np.asarray(leaf[0]))
    resizes = [e for e in eng.epochs if e.resized]
    assert [e.workers for e in resizes] == [3, 4]
    # the W=4 programs were parked by the shrink and reused by the regrow
    assert any(k[-1] == 4 for k in resizes[0].parked)
    n = eng.compiles
    st, _ = eng.run_round(st, 4, 2, lr_fn)
    assert eng.compiles == n, "regrow to a parked W must not recompile"


@pytest.mark.parametrize("restore_layout", ["tree", "flat", "flat_sharded"])
def test_restore_elastic_across_worker_counts(tmp_path, restore_layout):
    """A checkpoint written at W=4 restores under W=3 (surviving lanes
    bitwise) and W=5 (the joined lane cloning lane 0 = consensus), into
    any layout."""
    src, lr_fn = _mk_engine(workers=4)
    st = src.init_state()
    st, _ = src.run_round(st, 0, 2, lr_fn)
    path = str(tmp_path / "ck")
    src.save(path, st, step=2)
    src_tree = jax.tree.map(np.asarray, F.to_tree_state(src.spec, st))

    for w in (3, 5):
        dst, _ = _mk_engine(workers=w, layout=restore_layout)
        got, step = dst.restore_elastic(path, dst.init_state())
        assert step == 2
        tree = (got if restore_layout == "tree"
                else F.to_tree_state(dst.spec, got))
        la = jax.tree.leaves(src_tree["params"])
        lb = jax.tree.leaves(tree["params"])
        for a, b in zip(la, lb):
            b = np.asarray(b)
            np.testing.assert_array_equal(a[:min(w, 4)], b[:min(w, 4)])
            if w == 5:
                np.testing.assert_array_equal(b[4], a[0])
        assert dst.h_trace == [(0, 2)]
        assert np.all(dst.membership == 1.0)
