"""Synchronization (model-averaging) transforms applied every H steps.

Paper-faithful sync (Alg. 2 line 15): the global iterate is the plain mean of
worker replicas; *optimizer state is not averaged* (Local AdamW keeps local
moments — matching the paper's implementation).

Beyond-paper options (recorded separately in EXPERIMENTS.md §Perf):
  * outer Nesterov momentum on the sync delta (DiLoCo-style),
  * int8-quantized sync deltas (README §Quantized sync: the wire carries
    quantized integer codes, cutting cross-pod DCI bytes per sync).
Both require an `anchor` (the params at the previous sync) carried in state.

## The RS-domain quantization rule

All quantized paths mean the integer *codes* q = clip(round(d/s*127)) and
dequantize once, after the mean: `step = (Σ_i q_i / W) * (s / 127)`.  Σq is a
sum of integers — exact in ANY summation order (|Σ| < 2^24) — so the worker
mean is bitwise-identical whether it runs as a local `jnp.mean`, a GSPMD
all-reduce, an explicit reduce_scatter, or a multi-process gloo collective.
That is what lets the three layouts (and real multi-host execution,
launch/multihost.py) stay bitwise-equal under quantization, which a mean of
dequantized f32 values (the previous formulation) cannot guarantee.

Per-tensor scales are max statistics, also exact under any fold: on the
sharded layout each device computes *shard-local partial amaxes* per tensor
and one tiny `pmax` over the whole mesh folds them ([Σ #leaves] floats — the
only collective besides the RS/AG legs; no GSPMD per-element scale
collectives).

Layouts (`make_sync(run_cfg, spec=...)`):
  * tree (spec=None) — state mirrors the model pytree; the worker mean
    lowers to one all-reduce per leaf and every quantize/momentum op
    round-trips HBM separately.
  * flat (spec=FlatParamSpace) — state holds one [W, N] buffer per dtype
    bucket (core/flat.py); the mean is one all-reduce per bucket, and the
    quantize + momentum + anchor math runs as one fused pass
    (kernels/sync_update.py).  Per-tensor quantization scales are preserved
    via the spec's segment reductions, keeping the two layouts bitwise-equal.
  * flat_sharded (spec=ShardedFlatSpace carrying a mesh) — the worker mean
    decomposes into its two halves, written as explicit collectives: one
    `psum_scatter` (reduce_scatter) and one `all_gather` per dtype bucket.
    Quantized, the two legs carry the integer codes in the exact
    accumulation dtype (int16 while W*127 < 2^15, else int32) — half the
    f32 wire bytes — and the amax fold above replaces the GSPMD scale
    collectives.  Without a mesh the same state layout runs the flat path
    above on the padded buffers, bitwise-equal to tree/flat.

## Wire modes (README §Wire modes)

`run_cfg.sync_wire` picks what the quantized payload looks like on a wire:
"auto" keeps the exact Σq contract above (codes travel in `wire_dtype(W)`,
int16/int32, so the on-wire sum never overflows); "ring-int8" replaces the
one-shot reduce_scatter with a W-hop re-quantizing `ppermute` ring that
keeps int8 on every hop at the price of measured (never assumed) per-hop
requantization noise — see the ring section below.

The two halves are also exposed separately (`make_sync_begin` /
`make_sync_apply`) so the RoundEngine's `--sync overlap` mode can issue the
reduce at the round boundary and defer the gather/apply past the first local
steps of the next round (core/engine.py).  Quantized pending syncs are
`{"q": codes-mean-or-sum, "scale": per-element scales}` — the apply leg
dequantizes and runs the outer update in one fused pass
(kernels/sync_update.py `sync_apply_update`).

## Partial participation (`--sync partial`, README §Elastic training)

`partial=True` variants of the two halves take a per-round membership mask
m ∈ {0,1}^W: the mean runs over the workers that ARRIVED, Σ_i m_i x_i / |P|
with |P| = Σ m.  Absent lanes are masked out of the delta BEFORE the scale
statistic and the quantizer, so (a) the per-tensor amax is exactly the
participant amax (|0| never raises a max), (b) an absent worker's codes are
exactly 0 (contributing nothing to Σq), and (c) the mean stays exact in the
integer-code domain: Σ_{i∈P} q_i is an integer sum in any collective order,
divided by |P| once at apply time — bitwise identical to a W'=|P| run over
the participant rows (tests/test_elastic.py, multihost --mode partial).
With m = 1 everywhere the partial sync is bitwise the blocking sync for
power-of-two W (x·1.0 is exact, and Σ/W — true IEEE division — matches
jnp.mean's multiply-by-reciprocal lowering exactly iff the divisor is a
power of two; for other |P| the partial path itself, m = 1 on the
participant rows, is the bitwise reference).  The exact apply broadcasts the consensus to
ALL W lanes — absent workers re-anchor to consensus the moment they rejoin,
which is what makes local-gradient training naturally fault-tolerant: a
worker lost mid-round costs only its local steps since the last boundary.
The ring wire does not compose with partial masks (the running-mean fold
bakes W into every hop) and raises.

## Profiling

Every sync function built here (the fused flat sync, `begin`, `apply` and
what composes them) runs under `jax.named_scope("sync")`: the ops it
lowers to carry `sync` in their HLO `op_name`, which a device profile
reads to put a round's time down to the sync.  Metadata only — no device
work, no change to the numerics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.errors import ConfigError, LayoutError
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def worker_mean(tree):
    """Mean over the leading worker axis, broadcast back — lowers to a single
    all-reduce over the worker mesh axes under GSPMD (per leaf; per dtype
    bucket when `tree` is a FlatParamSpace bucket dict)."""
    def one(x):
        m = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape).astype(x.dtype)
    return jax.tree.map(one, tree)


def _guarded_scale(amax):
    """int8 scale from a max-|delta| statistic.  Guarded: an all-zero delta
    keeps scale 1 so the round-trip is exactly zero.  (The previous
    `amax + 1e-12` additive guard systematically shrank dequantized values
    by amax/(amax+1e-12) — a 50% bias when amax ~ 1e-12.)"""
    return jnp.where(amax > 0.0, amax, 1.0)


def _quantize_codes(d, scale):
    """Integer codes of a delta under elementwise (broadcastable) scales:
    clip(round(d/s*127)) ∈ [-127, 127], kept in f32 (integer-valued — the
    domain every quantized worker mean runs in)."""
    return jnp.clip(jnp.round(d / scale * 127.0), -127.0, 127.0)


def _quantize_delta(delta):
    """Symmetric per-tensor int8 round-trip of a delta pytree — the
    reference a single worker's wire codes dequantize to (property-tested in
    tests/test_quantize_props.py)."""
    def one(d):
        a = _guarded_scale(jnp.max(jnp.abs(d)))
        q = _quantize_codes(d, a)
        return q.astype(jnp.int8).astype(jnp.float32) * (a / 127.0)
    return jax.tree.map(one, delta)


def flat_delta_scales(spec, bucket: str, p, anchor, mask=None):
    """Per-tensor int8 scales for one flat bucket, spread to elements [N].

    Identical statistics to the tree path: max|p - anchor| over the worker
    axis and every element of each leaf (max is exact, so the segment
    reduction matches per-leaf `jnp.max` bitwise).  A membership `mask`
    ([W] f32) zeroes absent lanes' deltas first, so the statistic is
    exactly the participant amax (|0| never raises a max)."""
    d = jnp.abs(p.astype(jnp.float32) - anchor.astype(jnp.float32)[None])
    if mask is not None:
        d = d * mask[:, None]
    d = jnp.max(d, axis=0)
    return spec.spread(bucket, _guarded_scale(spec.segment_max(bucket, d)))


def partial_segment_amax(d, seg, n_segments: int):
    """Shard-local per-tensor partial amax of one bucket block: d [W_loc,
    n_blk] delta rows, seg [n_blk] local segment ids -> [n_segments] f32.
    Segments absent from this shard report the max-identity (-inf); a max
    fold over all shards (np.maximum / lax.pmax) therefore reconstructs the
    full-tensor amax *exactly* — max is exact, so shard-local partials fold
    to bitwise the unsharded statistic for arbitrary splits (property-tested
    in tests/test_quantize_props.py)."""
    return jax.ops.segment_max(jnp.max(jnp.abs(d), axis=0), seg,
                               num_segments=n_segments)


def wire_dtype(w: int, accum: int | None = None):
    """Smallest integer dtype that holds the on-wire accumulation of int8
    codes exactly — the RS/AG payload type for quantized sharded sync.

    `accum` is the number of codes summed *at once on the wire*: the one-shot
    reduce_scatter folds all W workers in one collective (accum=W, the
    default), so the payload must hold Σq = ±W·127; the re-quantizing ring
    (`--wire ring-int8`) never sums on the wire — each hop carries one freshly
    quantized partial MEAN (accum=1), so int8 always suffices mid-hop."""
    accum = w if accum is None else accum
    if accum <= 1:
        return jnp.int8
    return jnp.int16 if accum * 127 < 2 ** 15 else jnp.int32


# --------------------------------------------------------------------------
# The decomposed sync: reduce (scatter leg) | gather + outer update + apply
# --------------------------------------------------------------------------

def _axt(axes: tuple[str, ...]):
    """Mesh-axis tuple -> PartitionSpec entry (the shared normalization)."""
    from repro.core.flat import axis_entry
    return axis_entry(axes)


def _use_collectives(spec) -> bool:
    """True when `spec` is a mesh-carrying ShardedFlatSpace with a real
    worker axis — the explicit reduce_scatter/all_gather decomposition."""
    return (getattr(spec, "mesh", None) is not None
            and bool(getattr(spec, "worker_axes", ())))


def _rs_mean(spec, x, w: int, mask=None):
    """[W, N] bucket -> worker-mean chunks [W, N/W] via ONE reduce_scatter
    over the worker axes: device (worker i, shard s) ends up owning the i-th
    contiguous 1/W sub-chunk of shard s's mean.  With a membership `mask`
    ([W] f32) the mean runs over the participants only: absent lanes are
    zeroed before the reduce and the divisor is |P| = Σ mask."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)

    if mask is None:
        def body(d):
            s = jax.lax.psum_scatter(d, spec.worker_axes,
                                     scatter_dimension=1, tiled=True)
            return s / w

        return shard_map_compat(body, spec.mesh, in_specs=P(wt, st),
                                out_specs=P(wt, st))(x)

    def body(d, m):
        cnt = jax.lax.psum(m[0], spec.worker_axes)
        s = jax.lax.psum_scatter(d * m[0], spec.worker_axes,
                                 scatter_dimension=1, tiled=True)
        return s / cnt

    return shard_map_compat(body, spec.mesh, in_specs=(P(wt, st), P(wt)),
                            out_specs=P(wt, st))(x, mask)


def _ag_mean(spec, pending):
    """Inverse leg: gather the worker-owned chunks [W, N/W] back into the
    full consensus [N] (replicated over workers) via ONE all_gather."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)

    def body(s):
        return jax.lax.all_gather(s, spec.worker_axes, axis=1, tiled=True)

    out = shard_map_compat(body, spec.mesh, in_specs=P(wt, st),
                           out_specs=P(None, st))(pending)
    return out[0]


def _rs_quantized_begin(spec, params, anchor, mask=None):
    """The RS-domain quantized reduce, all dtype buckets in ONE shard_map.

    Per device: local delta block, shard-local partial amaxes per tensor,
    one tiny `pmax` over the whole mesh (a [Σ #leaves]-float fold — the only
    scale collective), int8 codes, then ONE psum_scatter per bucket carrying
    the codes in the exact accumulation dtype (`wire_dtype`).  Returns
    pending {"q": {bucket: [W, N/W] int}, "scale": {bucket: [N] f32}} — "q"
    holds the *sum* Σq (still to be divided by W at apply time).

    With a membership `mask` ([W] f32) each absent lane's delta is zeroed
    BEFORE the amax and the quantizer: scales come from participants only,
    absent codes are exactly 0, so the psum_scatter yields Σ_{i∈P} q_i and
    the pending gains {"count": |P|} for the apply-time division."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)
    buckets = spec.buckets
    nseg = {b: spec.bucket_leaves(b) for b in buckets}
    seg = {b: jnp.asarray(spec.segment_ids(b)) for b in buckets}
    w = jax.tree.leaves(params)[0].shape[0]
    wdt = wire_dtype(w)

    def body(p, a, sg, *m):
        d = {b: p[b].astype(jnp.float32) - a[b].astype(jnp.float32)[None]
             for b in buckets}
        if m:
            d = {b: d[b] * m[0][0] for b in buckets}
        part = jnp.concatenate(
            [partial_segment_amax(d[b], sg[b], nseg[b]) for b in buckets])
        full = jax.lax.pmax(part, spec.worker_axes + spec.shard_axes)
        off, scales = 0, {}
        for b in buckets:
            per_leaf = _guarded_scale(full[off:off + nseg[b]])
            off += nseg[b]
            # clamped gather == spec.spread: pad ids read the last leaf's
            # scale, harmless — pad deltas are exactly zero
            scales[b] = per_leaf[sg[b]]
        qs = {b: jax.lax.psum_scatter(
                  _quantize_codes(d[b], scales[b][None]).astype(wdt),
                  spec.worker_axes, scatter_dimension=1, tiled=True)
              for b in buckets}
        return qs, scales

    in_specs = [{b: P(wt, st) for b in buckets},
                {b: P(st) for b in buckets},
                {b: P(st) for b in buckets}]
    out_specs = ({b: P(wt, st) for b in buckets},
                 {b: P(st) for b in buckets})
    args = [params, anchor, seg]
    if mask is not None:
        in_specs.append(P(wt))
        args.append(mask)
    qs, scales = shard_map_compat(body, spec.mesh,
                                  in_specs=tuple(in_specs),
                                  out_specs=out_specs)(*args)
    out = {"q": qs, "scale": scales}
    if mask is not None:
        out["count"] = jnp.sum(mask)
    return out


def _ag_codes(spec, qs):
    """Gather leg of the quantized sync: the worker-owned Σq chunks [W, N/W]
    back to the full [N] code sums via ONE all_gather per bucket (one
    shard_map; the payload stays in the integer wire dtype)."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)

    def body(s):
        return {b: jax.lax.all_gather(s[b], spec.worker_axes, axis=1,
                                      tiled=True) for b in s}

    out = shard_map_compat(body, spec.mesh,
                           in_specs=({b: P(wt, st) for b in qs},),
                           out_specs={b: P(None, st) for b in qs})(qs)
    return {b: out[b][0] for b in out}


# --------------------------------------------------------------------------
# The re-quantizing int8 ring (`--wire ring-int8`)
# --------------------------------------------------------------------------
#
# The exact Σq contract forces wire_dtype(W) — int16/int32 — onto the
# reduce_scatter: partial sums of int8 codes overflow int8.  The ring mode
# drops the exact-sum contract instead: the W-hop ppermute ring maintains the
# running partial MEAN, whose magnitude never exceeds the largest
# contributor's delta, and re-quantizes it to int8 with a fresh shard-local
# scalar scale at every hop — int8 payload on every wire, 2-4x fewer bytes.
# The price is per-hop requantization noise (at most half a level, scale/254,
# per hop); it is MEASURED, not assumed: benchmarks/sde_drift.py runs the
# exact-vs-ring A/B and launch/autotune.py records the drift next to the
# bytes.  Cross-layout/cross-process claims are therefore tolerance-based
# (`ring_tolerance`), never bitwise — deliberately beyond-exact semantics.

WIRE_MODES = ("auto", "ring-int8")


def check_wire(run_cfg) -> str:
    """Validate + return the wire mode.  ring-int8 rides the quantized sync
    machinery (codes + anchor), so it requires sync_quantize."""
    wire = getattr(run_cfg, "sync_wire", "auto")
    if wire not in WIRE_MODES:
        raise ValueError(f"unknown sync_wire {wire!r}; pick from {WIRE_MODES}")
    if wire == "ring-int8" and not run_cfg.sync_quantize:
        raise ValueError("sync_wire='ring-int8' requires sync_quantize=True "
                         "(the ring carries int8 codes of the delta)")
    return wire


def ring_tolerance(w: int, amax, rounds: int = 1):
    """Worst-case |ring mean - exact mean| bound after `rounds` syncs whose
    per-tensor delta amax never exceeded `amax`.

    Per sync: hop k's requantization errs at most s_k/254 <= amax/254 per
    element, attenuated by the remaining mean folds to k/W of that at the
    end; summed over hops plus the final (gather-leg) quantize:
        err <= amax/254 * (Σ_{k=1..W-1} k/W + 1) = amax/254 * (W+1)/2
    Errors across rounds add at most linearly (each round's params feed the
    next delta).  A 2x safety factor absorbs the f32 rounding of the
    fold itself."""
    return float(amax) * (w + 1) / 254.0 * rounds * 2.0


def _linear_worker_index(mesh, axes: tuple[str, ...]):
    """Traced linear index of this device along the worker axes, row-major
    over the tuple — matching how `ppermute` linearizes multi-axis names."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _ring_quantized_begin(spec, params, anchor):
    """The int8 ring reduce, all dtype buckets in ONE shard_map.

    Per device the bucket block [1, n_loc] splits into W contiguous
    sub-chunks; worker j seeds the partial destined for worker (j-1) mod W
    and the ring rotates W-1 times, each hop carrying ONE freshly int8-
    quantized partial mean + its f32 scalar scale (jax.lax.ppermute over the
    worker axes — `hlo_analysis` sees W-1 s8 collective-permutes per bucket
    and zero int16/int32 payloads).  The arriving partial is dequantized and
    folded with the local sub-chunk by the fused per-hop requant pass
    (kernels `ring_combine` / `ring_quantize_codes`).  After the last hop
    worker j owns the full W-mean of sub-chunk j, quantized one final time
    for the (deferrable) int8 all_gather leg.

    Returns pending {"q": {bucket: [W, N/W] int8 mean codes},
    "scale": {bucket: [W, S] f32 per-chunk scales}} — unlike the exact path
    the codes already ARE the mean (no /W at apply time) and the scales are
    per ring chunk, not per tensor."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)
    buckets = spec.buckets
    w = jax.tree.leaves(params)[0].shape[0]
    perm = [(j, (j + 1) % w) for j in range(w)]
    waxes = spec.worker_axes

    def body(p, a):
        i = _linear_worker_index(spec.mesh, waxes)
        qs, ss = {}, {}
        for b in buckets:
            d = p[b].astype(jnp.float32) - a[b].astype(jnp.float32)[None]
            n_loc = d.shape[1]
            if n_loc % w != 0:  # spec pads to W*S chunks
                raise LayoutError(
                    f"ring bucket {b!r}: shard length {n_loc} not divisible "
                    f"by {w} workers")
            dc = d[0].reshape(w, n_loc // w)
            # seed: the partial destined for worker (i-1) mod W
            acc = jnp.take(dc, (i - 1) % w, axis=0)
            s = _guarded_scale(jnp.max(jnp.abs(acc)))
            q = kops.ring_quantize_codes(acc, s)
            for k in range(1, w):
                q = jax.lax.ppermute(q, waxes, perm)
                s = jax.lax.ppermute(s, waxes, perm)
                acc, amax = kops.ring_combine(
                    q, s, jnp.take(dc, (i - 1 - k) % w, axis=0), k)
                s = _guarded_scale(amax)
                q = kops.ring_quantize_codes(acc, s)
            qs[b] = q[None]
            ss[b] = jnp.reshape(s, (1, 1))
        return qs, ss

    in_specs = ({b: P(wt, st) for b in buckets},
                {b: P(st) for b in buckets})
    out_specs = ({b: P(wt, st) for b in buckets},
                 {b: P(wt, st) for b in buckets})
    qs, ss = shard_map_compat(body, spec.mesh, in_specs=in_specs,
                              out_specs=out_specs)(params, anchor)
    return {"q": qs, "scale": ss}


def _ag_ring(spec, pending):
    """Gather leg of the ring sync: ONE int8 all_gather per bucket brings
    every worker's mean sub-chunk (and its scalar scale) to all workers;
    codes are spread back to per-element scales locally — nothing but int8
    payloads and scalar-sized f32 scales ever cross a wire.  Returns
    (step_in {bucket: [N] f32 mean codes}, scales {bucket: [N] f32})."""
    from repro.models.common import shard_map_compat

    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)
    buckets = list(pending["q"])

    def body(qs, ss):
        step, scl = {}, {}
        for b in buckets:
            qg = jax.lax.all_gather(qs[b], spec.worker_axes, axis=1,
                                    tiled=True)              # [1, n_loc] s8
            sg = jax.lax.all_gather(ss[b], spec.worker_axes, axis=1,
                                    tiled=True)              # [1, W] f32
            w = sg.shape[1]
            step[b] = qg.astype(jnp.float32)
            scl[b] = jnp.repeat(sg[0], qg.shape[1] // w)[None]
        return step, scl

    in_specs = ({b: P(wt, st) for b in buckets},
                {b: P(wt, st) for b in buckets})
    out_specs = ({b: P(None, st) for b in buckets},
                 {b: P(None, st) for b in buckets})
    step, scl = shard_map_compat(body, spec.mesh, in_specs=in_specs,
                                 out_specs=out_specs)(pending["q"],
                                                      pending["scale"])
    return ({b: step[b][0] for b in buckets}, {b: scl[b][0] for b in buckets})


def ring_codes_host(d, w: int | None = None):
    """Mesh-less emulation of the int8 ring over one bucket delta d [W, N]
    (S=1 chunking), identical per-hop arithmetic to `_ring_quantized_begin`:
    chunk c's partial seeds at worker (c+1) mod W and folds each visitor's
    contribution through the same fused requant pass.  Returns
    (q [W, ceil(N/W)] int8 mean codes, s [W] f32 per-chunk scales) — the
    host reference the drift A/B (benchmarks/sde_drift.py) and the multihost
    tolerance assertions run against."""
    w = d.shape[0] if w is None else w
    n = d.shape[1]
    pad = (-n) % w
    if pad:
        d = jnp.pad(d, ((0, 0), (0, pad)))  # zero delta: exact under requant
    dc = d.reshape(w, w, d.shape[1] // w)   # [worker, chunk, chunk_len]
    qs, ss = [], []
    for c in range(w):
        j0 = (c + 1) % w
        acc = dc[j0, c]
        s = _guarded_scale(jnp.max(jnp.abs(acc)))
        q = kops.ring_quantize_codes(acc, s)
        for k in range(1, w):
            acc, amax = kops.ring_combine(q, s, dc[(j0 + k) % w, c], k)
            s = _guarded_scale(amax)
            q = kops.ring_quantize_codes(acc, s)
        qs.append(q)
        ss.append(s)
    return jnp.stack(qs), jnp.stack(ss)


def _ring_host_begin(spec, params, anchor):
    """Mesh-less ring pending for the flat layouts: per bucket
    {"q": [W, C] int8, "scale": [W] f32} with C = ceil(N/W)."""
    out_q, out_s = {}, {}
    for b in spec.buckets:
        d = (params[b].astype(jnp.float32)
             - anchor[b].astype(jnp.float32)[None])
        out_q[b], out_s[b] = ring_codes_host(d)
    return {"q": out_q, "scale": out_s}


def _ring_host_gather(pending, anchor):
    """Flatten mesh-less ring pending back to per-element (step_in, scales)
    matching `_ag_ring`'s output — same fused apply path either way."""
    step, scl = {}, {}
    for b in pending["q"]:
        q, s = pending["q"][b], pending["scale"][b]
        n = anchor[b].shape[0]
        step[b] = q.reshape(-1)[:n].astype(jnp.float32)
        scl[b] = jnp.repeat(s, q.shape[1])[:n]
    return step, scl


def pending_specs(run_cfg, spec):
    """PartitionSpec tree of the pending sync (`make_sync_begin`'s output)
    under a mesh-carrying ShardedFlatSpace — what a program that *threads*
    the pending across its boundary (the RoundEngine's overlap round,
    launch/shapes.py's lowering case) declares as the in/out sharding.

    The reduce_scatter leg leaves the pending worker-sharded: each device
    owns the 1/W sub-chunk of its shard it reduced, so payloads sit at
    [W, N/W] over (worker_axes, shard_axes).  Quantized pending carries the
    integer code-sums at that sharding plus the per-element scales, which
    are shard-local only ([N] over shard_axes).  Ring pending differs: the
    scales are per ring chunk — one scalar per (worker, shard) device — so
    they share the payload's (worker_axes, shard_axes) sharding."""
    wt, st = _axt(spec.worker_axes), _axt(spec.shard_axes)
    payload = {b: P(wt, st) for b in spec.buckets}
    if run_cfg.sync_quantize:
        if check_wire(run_cfg) == "ring-int8":
            return {"q": payload, "scale": dict(payload)}
        return {"q": payload, "scale": {b: P(st) for b in spec.buckets}}
    return payload


def make_sync_begin(run_cfg, spec=None, partial: bool = False):
    """First half of the sync: the reduce.  begin(state) -> pending, a pure
    function of the pre-sync state (no state mutation).

    pending per bucket/leaf: the worker-mean params in f32 (plain sync), the
    worker-mean delta from the anchor (momentum-only sync), or — quantized —
    {"q": worker-mean integer codes, "scale": per-element scales}.  Under a
    mesh-carrying ShardedFlatSpace the mean is an explicit psum_scatter over
    the worker axes — one reduce_scatter per dtype bucket on the wire,
    carrying integer codes when quantized — and pending stays worker-sharded
    [W, N/W] (codes as the un-divided sum Σq); the matching all_gather lives
    in make_sync_apply (the deferrable leg).

    partial=True: begin(state, mask) with mask [W] f32 ∈ {0,1} — the mean
    runs over the participants only (module docstring §Partial
    participation).  Plain/momentum pendings arrive already divided by |P|;
    quantized pendings carry the undivided Σ_{i∈P} q_i plus {"count": |P|}
    for the apply-time division (the exact integer-code domain)."""
    quantize = run_cfg.sync_quantize
    mom = run_cfg.outer_momentum
    wire = check_wire(run_cfg)
    coll = _use_collectives(spec)
    if wire == "ring-int8" and spec is None:
        raise ValueError("sync_wire='ring-int8' needs a flat layout "
                         "(--param-layout flat | flat_sharded): the ring "
                         "chunks a bucket, not a pytree leaf")
    if wire == "ring-int8" and partial:
        raise ValueError("sync_wire='ring-int8' does not compose with "
                         "partial participation: the running-mean ring "
                         "bakes W into every hop — use wire='auto'")

    def mean_w(x, mask=None):
        if coll:
            return _rs_mean(spec, x, x.shape[0], mask)
        if mask is None:
            return jnp.mean(x, axis=0)
        shape = (mask.shape[0],) + (1,) * (x.ndim - 1)
        return jnp.sum(x * mask.reshape(shape), axis=0) / jnp.sum(mask)

    @jax.named_scope("sync")
    def begin(state, mask=None):
        params = state["params"]
        if not quantize and mom == 0.0:
            return jax.tree.map(
                lambda p: mean_w(p.astype(jnp.float32), mask), params)
        anchor = state["anchor"]
        if wire == "ring-int8":
            return (_ring_quantized_begin(spec, params, anchor) if coll
                    else _ring_host_begin(spec, params, anchor))
        if quantize and coll:
            return _rs_quantized_begin(spec, params, anchor, mask)
        delta = jax.tree.map(
            lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32)[None],
            params, anchor)
        if mask is not None and not coll:
            # zero absent lanes BEFORE the scale statistic and the quantizer
            # (the collective paths mask inside their shard_map bodies)
            delta = jax.tree.map(
                lambda d: d * mask.reshape((mask.shape[0],)
                                           + (1,) * (d.ndim - 1)), delta)
        if quantize:
            if spec is None:
                scales = jax.tree.map(
                    lambda d: _guarded_scale(jnp.max(jnp.abs(d))), delta)
            else:
                scales = {b: flat_delta_scales(spec, b, params[b], anchor[b],
                                               mask)
                          for b in spec.buckets}
            if mask is None:
                qmean = jax.tree.map(
                    lambda d, s: jnp.mean(_quantize_codes(d, s[None] if
                                                          jnp.ndim(s) else s),
                                          axis=0),
                    delta, scales)
                return {"q": qmean, "scale": scales}
            qsum = jax.tree.map(
                lambda d, s: jnp.sum(_quantize_codes(d, s[None] if
                                                     jnp.ndim(s) else s),
                                     axis=0),
                delta, scales)
            return {"q": qsum, "scale": scales, "count": jnp.sum(mask)}
        if coll:
            return jax.tree.map(lambda d: mean_w(d, mask), delta)
        if mask is not None:   # delta already masked above
            return jax.tree.map(
                lambda d: jnp.sum(d, axis=0) / jnp.sum(mask), delta)
        return jax.tree.map(lambda d: jnp.mean(d, axis=0), delta)

    if partial:
        def begin_partial(state, mask):
            return begin(state, mask)
        return begin_partial
    return begin


def make_sync_apply(run_cfg, spec=None, partial: bool = False):
    """Second half of the sync: gather + outer update + apply.

    apply(state, pending, entry_params=None) -> state.
      * entry_params=None — exact mode: params become the consensus
        directly; composed right after begin() this is the blocking sync,
        and deferred one program later with no steps in between (overlap
        depth 0) it stays bitwise the blocking trajectory.
      * entry_params given (the params begin() saw) — correction mode for
        overlap depth > 0: each worker keeps the local progress it made
        while the reduce was in flight, x_i <- x_i + (consensus - entry_i).
    Under a mesh-carrying ShardedFlatSpace the gather is an explicit
    all_gather over the worker axes — the deferred leg of the decomposed
    all-reduce; quantized it carries the integer code sums, divided by W and
    dequantized here (fused with the outer Nesterov + anchor update in one
    kernels/sync_update.py `sync_apply_update` pass per bucket).

    partial=True pendings (make_sync_begin(..., partial=True)) carry the
    participant count when quantized: the code sums divide by |P| =
    pending["count"] instead of W.  The exact apply (entry_params=None)
    still broadcasts the consensus to ALL W lanes — absent workers
    re-anchor to consensus on rejoin."""
    quantize = run_cfg.sync_quantize
    mom = run_cfg.outer_momentum
    wire = check_wire(run_cfg)
    coll = _use_collectives(spec)
    del partial  # pendings self-describe via their "count" entry

    def gather(x):
        return _ag_mean(spec, x) if coll else x

    def to_params(consensus, params, entry):
        if entry is None:
            return jax.tree.map(
                lambda c, p: jnp.broadcast_to(c[None], p.shape
                                              ).astype(p.dtype),
                consensus, params)
        return jax.tree.map(
            lambda c, p, e: (p.astype(jnp.float32)
                             + (c[None] - e.astype(jnp.float32))
                             ).astype(p.dtype),
            consensus, params, entry)

    @jax.named_scope("sync")
    def apply(state, pending, entry_params=None):
        params = state["params"]
        if not quantize and mom == 0.0:
            mean = jax.tree.map(gather, pending)
            return {**state, "params": to_params(mean, params, entry_params)}
        new_state = dict(state)
        if quantize:
            if wire == "ring-int8":
                # the ring already holds the MEAN (no /W); scales arrive per
                # ring chunk and spread to elements with the gather
                step_in, scales = (_ag_ring(spec, pending) if coll else
                                   _ring_host_gather(pending, state["anchor"]))
            elif coll:
                div = pending.get("count")
                if div is None:
                    div = jax.tree.leaves(params)[0].shape[0]
                qmean = {b: q.astype(jnp.float32) / div
                         for b, q in _ag_codes(spec, pending["q"]).items()}
                scales = pending["scale"]
                step_in = qmean
            else:
                cnt = pending.get("count")
                step_in = (pending["q"] if cnt is None else jax.tree.map(
                    lambda q: q / cnt, pending["q"]))
                scales = pending["scale"]
        else:
            step_in = jax.tree.map(gather, pending)
            scales = None
        mu_in = state["outer_mu"] if mom > 0.0 else None
        if spec is not None:
            new_anchor = {}
            new_mu = {} if mom > 0.0 else None
            for b in spec.buckets:
                a2, mu2 = kops.sync_apply_update(
                    step_in[b], state["anchor"][b],
                    scale=scales[b] if quantize else None,
                    mu=mu_in[b] if mom > 0.0 else None, momentum=mom)
                new_anchor[b] = a2
                if mom > 0.0:
                    new_mu[b] = mu2
        else:
            ls, treedef = jax.tree.flatten(step_in)
            la = treedef.flatten_up_to(state["anchor"])
            lsc = treedef.flatten_up_to(scales) if quantize else [None] * len(ls)
            lmu = treedef.flatten_up_to(mu_in) if mom > 0.0 else [None] * len(ls)
            outs = [kref.sync_apply_update(s, a, scale=sc, mu=m, momentum=mom)
                    for s, a, sc, m in zip(ls, la, lsc, lmu)]
            new_anchor = jax.tree.unflatten(treedef, [o[0] for o in outs])
            new_mu = (jax.tree.unflatten(treedef, [o[1] for o in outs])
                      if mom > 0.0 else None)
        new_state["anchor"] = new_anchor
        if mom > 0.0:
            new_state["outer_mu"] = new_mu
        new_state["params"] = to_params(new_anchor, params, entry_params)
        return new_state

    return apply


def make_sync(run_cfg, spec=None):
    """Returns sync(state) -> state.  state = {"params", "opt", "anchor"?,
    "outer_mu"?}; params carry a leading worker axis.  With `spec` (a
    core.flat.FlatParamSpace) the state is flat: params {bucket: [W, N]},
    anchor/outer_mu {bucket: [N]}.  A mesh-carrying ShardedFlatSpace
    composes the two explicit halves back-to-back: the blocking sync is then
    one reduce_scatter + one all_gather per bucket instead of a full
    all-reduce (quantized: integer-code payloads + one tiny amax pmax).
    A mesh-less flat spec runs the one-pass fused kernel instead."""
    quantize = run_cfg.sync_quantize
    mom = run_cfg.outer_momentum
    wire = check_wire(run_cfg)

    if spec is not None and not _use_collectives(spec) and wire != "ring-int8":
        @jax.named_scope("sync")
        def sync_flat(state):
            params = state["params"]
            if not quantize and mom == 0.0:
                return {**state, "params": worker_mean(params)}
            anchor = state["anchor"]
            new_state = dict(state)
            new_params, new_anchor = {}, {}
            new_mu = {} if mom > 0.0 else None
            for b in spec.buckets:
                p, a = params[b], anchor[b]
                scale = flat_delta_scales(spec, b, p, a) if quantize else None
                mu = state["outer_mu"][b] if mom > 0.0 else None
                p2, a2, mu2 = kops.sync_flat_update(p, a, scale=scale, mu=mu,
                                                    momentum=mom)
                new_params[b], new_anchor[b] = p2, a2
                if mom > 0.0:
                    new_mu[b] = mu2
            new_state["params"], new_state["anchor"] = new_params, new_anchor
            if mom > 0.0:
                new_state["outer_mu"] = new_mu
            return new_state

        return sync_flat

    # tree layout and the mesh-carrying sharded layout compose the two
    # explicit halves back-to-back (identical op sequence to the fused flat
    # kernel, so the layouts stay bitwise-equal)
    begin = make_sync_begin(run_cfg, spec)
    apply_ = make_sync_apply(run_cfg, spec)

    def sync_composed(state):
        return apply_(state, begin(state))

    return sync_composed


def make_sync_partial(run_cfg, spec=None):
    """Partial-participation sync: sync(state, mask) -> state, the two
    halves composed with a membership mask (module docstring §Partial
    participation).  Every layout runs the composed begin/apply — there is
    no fused partial kernel — so the mask semantics are identical across
    tree/flat/flat_sharded, and an all-ones mask is bitwise the composed
    blocking sync (which the flat fused kernel is proven equal to)."""
    begin = make_sync_begin(run_cfg, spec, partial=True)
    apply_ = make_sync_apply(run_cfg, spec, partial=True)

    def sync_partial(state, mask):
        return apply_(state, begin(state, mask))

    return sync_partial


SYNC_PROGRAMS = ("blocking", "partial", "begin", "apply")


def sync_program(run_cfg, spec=None, program: str = "blocking"):
    """The lowering seam for static analysis: one callable per sync
    sub-program, named.  `blocking` and `partial` are the whole-sync
    callables; `begin`/`apply` are the overlap halves — `begin` is the
    scatter leg a round boundary launches, `apply` the gather leg hidden
    behind the next round's first local steps.  The audit CLI
    (launch/audit.py) AOT-lowers each of these per (layout, wire, mesh)
    and evaluates the declarative rule registry against the HLO; nothing
    here executes."""
    if program == "blocking":
        return make_sync(run_cfg, spec=spec)
    if program == "partial":
        return make_sync_partial(run_cfg, spec=spec)
    if program == "begin":
        return make_sync_begin(run_cfg, spec=spec)
    if program == "apply":
        return make_sync_apply(run_cfg, spec=spec)
    raise ConfigError(
        f"unknown sync program {program!r}; pick from {SYNC_PROGRAMS}")
