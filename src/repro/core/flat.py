"""FlatParamSpace: the model pytree viewed as a few dtype-bucketed 1-D buffers.

Why: the two hot paths of the local-gradient runtime pay per-*tensor* costs
that a flat view eliminates.

  * Sync (every H steps) is a worker mean over the params pytree — under
    GSPMD that lowers to one all-reduce per leaf: hundreds of small,
    latency-bound collectives on transformer configs.  Over a flat buffer it
    is one all-reduce per dtype bucket (see launch/hlo_analysis
    `collective_counts`, which proves the drop).
  * The fused AdamW Pallas kernel launches once per leaf with per-leaf
    padding to its block size.  Over the flat fp32 bucket it launches once
    per local step, and pays at most one block of padding total.

The spec is recorded once at init: leaves are taken in pytree
(`jax.tree.flatten`) order and grouped into one contiguous 1-D buffer per
leaf dtype ("the dtype-bucket rule": elementwise math and collectives need a
homogeneous element type, and parameter dtypes are few — fp32 and/or bf16 —
so the collective count drops from O(#leaves) to O(#dtypes)).  Flatten and
unflatten are pure reshapes + concatenation/slices, so under XLA they fuse
into layout ops: gradients taken *with respect to the flat buffer* are
element-for-element identical to per-leaf gradients, which is what makes the
flat layout bitwise-equivalent to the tree layout (tests/test_flat.py).

Mirror trees (AdamW moments, SGD momentum, grads) share the params bucket
assignment — their leaves land at the same offsets, in their own dtype — so
`p[off:off+n]`, `m[off:off+n]`, `v[off:off+n]` always describe the same
tensor.

The tree layout remains available (`--param-layout tree`): it is the right
tool when you need per-tensor stats (debugging which layer diverges).

ShardedFlatSpace (`--param-layout flat_sharded`) extends the flat layout the
FSDP way: each dtype bucket is padded so it splits into per-device
*contiguous chunks* — the flat dim is sharded over the mesh axes that do NOT
carry the worker axis, so optimizer state and anchors are stored at 1/S per
device, and the every-H-steps worker mean decomposes into one
`reduce_scatter` (each worker reduces the 1/W chunk it owns) plus one
`all_gather` (rebuild the consensus) per bucket instead of a full
all-reduce.  The gather leg is what the RoundEngine's `--sync overlap` mode
defers into the next round (core/engine.py).  Because the chunk rule is
"pad, then split contiguously", the fsdp policy — whose per-leaf inner
shardings the plain flat layout cannot represent — gets a flat path too:
chunks replace per-tensor shardings.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.errors import LayoutError

Pytree = Any


@dataclasses.dataclass(frozen=True)
class _Leaf:
    """One pytree leaf's placement inside its dtype bucket."""
    bucket: str
    index: int           # segment id within the bucket (bucket-local order)
    offset: int          # element offset within the bucket buffer
    size: int
    shape: tuple[int, ...]


class FlatParamSpace:
    """Bidirectional view between a params pytree and dtype-bucketed buffers.

    Built once from the (abstract or concrete) single-replica params; after
    that, `flatten`/`unflatten` are pure layout ops.  `lead` counts leading
    batch-like axes shared by every leaf (the runtime's worker axis W):
    leaves `[*lead, *shape]` map to buffers `[*lead, N_bucket]`.
    """

    def __init__(self, tree: Pytree):
        leaves, self.treedef = jax.tree.flatten(tree)
        if not leaves:
            raise LayoutError("empty params pytree")
        self._leaves: list[_Leaf] = []
        sizes: dict[str, int] = {}
        order: dict[str, list[int]] = {}
        for i, x in enumerate(leaves):
            b = jnp.dtype(x.dtype).name
            off = sizes.get(b, 0)
            n = int(np.prod(x.shape, dtype=np.int64)) if x.shape else 1
            self._leaves.append(_Leaf(b, len(order.setdefault(b, [])), off, n,
                                      tuple(x.shape)))
            order[b].append(i)
            sizes[b] = off + n
        self.buckets: tuple[str, ...] = tuple(sorted(sizes))
        self.sizes: dict[str, int] = {b: sizes[b] for b in self.buckets}
        self._order = order           # bucket -> leaf indices, offset order
        self._seg: dict[str, np.ndarray] = {}

    # -- introspection -----------------------------------------------------

    @property
    def n_leaves(self) -> int:
        return len(self._leaves)

    def bucket_leaves(self, bucket: str) -> int:
        return len(self._order[bucket])

    def buffer_size(self, bucket: str) -> int:
        """Bucket-buffer length as materialized by `flatten` (the sharded
        subclass pads this up to a multiple of its chunk count)."""
        return self.sizes[bucket]

    def segment_ids(self, bucket: str) -> np.ndarray:
        """int32 [N_bucket]: which leaf (bucket-local index) each element of
        the bucket buffer belongs to — the per-tensor reduction map."""
        if bucket not in self._seg:
            seg = np.empty(self.sizes[bucket], np.int32)
            for i in self._order[bucket]:
                lf = self._leaves[i]
                seg[lf.offset:lf.offset + lf.size] = lf.index
            self._seg[bucket] = seg
        return self._seg[bucket]

    # -- layout ops --------------------------------------------------------

    def flatten(self, tree: Pytree, *, lead: int = 0) -> dict[str, jax.Array]:
        """Pytree (leaves `[*lead, *shape]`, shapes matching the spec) ->
        `{bucket: [*lead, N]}`.  Mirror trees may carry a different dtype
        per leaf (e.g. fp32 moments of bf16 params); within a bucket all
        mirror leaves must agree so the buffer stays homogeneous."""
        leaves, treedef = jax.tree.flatten(tree)
        if treedef != self.treedef:
            raise LayoutError(
                f"pytree structure {treedef} does not match the spec's "
                f"{self.treedef}")
        out = {}
        for b in self.buckets:
            parts = []
            for i in self._order[b]:
                x = leaves[i]
                lf = self._leaves[i]
                if tuple(x.shape[lead:]) != lf.shape:
                    raise LayoutError(
                        f"leaf {i} shape {tuple(x.shape)} (lead={lead}) does "
                        f"not match the spec's {lf.shape}")
                parts.append(jnp.reshape(x, x.shape[:lead] + (lf.size,)))
            out[b] = parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts, axis=lead)
        return out

    def unflatten(self, bufs: dict[str, jax.Array], *, lead: int = 0) -> Pytree:
        """`{bucket: [*lead, N]}` -> pytree of `[*lead, *shape]` leaves."""
        leaves: list[Any] = [None] * len(self._leaves)
        for b in self.buckets:
            buf = bufs[b]
            for i in self._order[b]:
                lf = self._leaves[i]
                sl = jax.lax.slice_in_dim(buf, lf.offset, lf.offset + lf.size,
                                          axis=lead)
                leaves[i] = jnp.reshape(sl, buf.shape[:lead] + lf.shape)
        return jax.tree.unflatten(self.treedef, leaves)

    def worker_views(self, bufs: dict[str, jax.Array]) -> Pytree:
        """`{bucket: [W, N]}` -> pytree of `[W, *shape]` leaves, sliced one
        worker at a time: the values of `unflatten(bufs, lead=1)`, but the
        TPU compiler takes minutes over a batched slice + reshape of a
        model-sized bucket and about a second over W unbatched ones.  Only
        for workers on one device: with W sharded over a mesh, a per-worker
        slice would move data between devices."""
        w = next(iter(bufs.values())).shape[0]
        per = [self.unflatten({b: x[i] for b, x in bufs.items()})
               for i in range(w)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    def worker_buffers(self, tree: Pytree) -> dict[str, jax.Array]:
        """Inverse of `worker_views`: `[W, *shape]` leaves -> `{bucket:
        [W, N]}`, flattened one worker at a time."""
        w = jax.tree.leaves(tree)[0].shape[0]
        per = [self.flatten(jax.tree.map(lambda x: x[i], tree))
               for i in range(w)]
        return {b: jnp.stack([p[b] for p in per]) for b in per[0]}

    # -- per-tensor reductions over the flat buffer ------------------------

    def segment_max(self, bucket: str, x: jax.Array) -> jax.Array:
        """Per-leaf max of an `[N]` bucket-shaped array -> `[#leaves]`.
        max is exact (no rounding), so this equals per-tensor `jnp.max`."""
        return jax.ops.segment_max(x, jnp.asarray(self.segment_ids(bucket)),
                                   num_segments=self.bucket_leaves(bucket))

    def spread(self, bucket: str, per_leaf: jax.Array) -> jax.Array:
        """Gather `[#leaves]` per-tensor values back to elements `[N]`."""
        return per_leaf[jnp.asarray(self.segment_ids(bucket))]


class ShardedFlatSpace(FlatParamSpace):
    """FlatParamSpace whose buckets split into per-device contiguous chunks.

    Each dtype bucket is zero-padded to a multiple of `shards` so that it
    divides evenly into `shards` contiguous chunks (FSDP-style).  `shards`
    should be W * S — worker count times the product of the flat-dim mesh
    axes — so both the storage sharding (S chunks) and the sync
    reduce_scatter (each worker owns 1/W of a chunk) land on whole-element
    boundaries.  Padding is invisible to `unflatten` (leaf offsets never
    reach it) and inert in the runtime: pad params/grads/moments start and
    stay exactly zero, pad deltas quantize to zero, and the pad's segment id
    sits outside [0, #leaves) so `segment_max` drops it.

    When built with a `mesh` (plus the worker/shard axis names), the sync
    path (core/sync.py) expresses the worker mean as an explicit
    `psum_scatter` + `all_gather` over `worker_axes` via shard_map — one
    reduce_scatter and one all_gather per bucket on the wire.  Without a
    mesh (single-process tests, the host training loop) the same state
    layout runs the plain-jnp flat path, bitwise-equal to layouts tree/flat.
    """

    def __init__(self, tree: Pytree, shards: int = 1, *, mesh=None,
                 worker_axes: tuple[str, ...] = (),
                 shard_axes: tuple[str, ...] = ()):
        super().__init__(tree)
        if shards < 1:
            raise LayoutError(f"shards must be >= 1, got {shards}")
        self.shards = shards
        self.mesh = mesh
        self.worker_axes = tuple(worker_axes)
        self.shard_axes = tuple(shard_axes)
        self.pad: dict[str, int] = {b: (-n) % shards
                                    for b, n in self.sizes.items()}

    def buffer_size(self, bucket: str) -> int:
        """Padded bucket-buffer length (a multiple of `shards`)."""
        return self.sizes[bucket] + self.pad[bucket]

    def flatten(self, tree: Pytree, *, lead: int = 0) -> dict[str, jax.Array]:
        out = super().flatten(tree, lead=lead)
        for b, x in out.items():
            if self.pad[b]:
                widths = [(0, 0)] * lead + [(0, self.pad[b])]
                out[b] = jnp.pad(x, widths)
        return out

    def segment_ids(self, bucket: str) -> np.ndarray:
        """Like the base map, extended over the pad with id == #leaves —
        out of range for `segment_max` (pad never contaminates a leaf's
        statistic) and clamped by `spread`'s gather (pad elements read the
        last leaf's value, harmless: their delta is exactly zero)."""
        if bucket not in self._seg:
            base = super().segment_ids(bucket)
            if self.pad[bucket]:
                ext = np.full(self.pad[bucket], self.bucket_leaves(bucket),
                              np.int32)
                self._seg[bucket] = np.concatenate([base, ext])
        return self._seg[bucket]


# --------------------------------------------------------------------------
# State shardings for the flat layouts
# --------------------------------------------------------------------------

def axis_entry(axes):
    """Mesh-axis name tuple -> PartitionSpec entry (None / name / tuple) —
    the one normalization every mesh-carrying call site shares."""
    if not isinstance(axes, tuple):
        return axes                       # already a name or None
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def flat_state_specs(run_cfg, waxes, spec):
    """PartitionSpec tree for the flat runtime state.  `waxes` is the worker
    mesh-axis tuple (or an already-normalized PartitionSpec entry).

    Plain flat: the worker axis over the worker mesh axes; the flat dim
    replicated (per-leaf inner shardings don't survive concatenation).
    flat_sharded: the flat dim additionally splits into contiguous chunks
    over the non-worker mesh axes — params AND optimizer moments stored at
    1/S per device, anchors/outer momentum likewise — which is what lets
    the fsdp policy run a flat layout at all."""
    from jax.sharding import PartitionSpec as P
    waxes = axis_entry(waxes)
    flat_dim = axis_entry(getattr(spec, "shard_axes", ()))
    bufs = lambda lead: {b: P(*(lead + (flat_dim,))) for b in spec.buckets}
    wlead, alead = (waxes,), ()
    if run_cfg.optimizer == "sgd":
        opt = {"mu": bufs(wlead), "step": P()}
    else:
        opt = {"m": bufs(wlead), "v": bufs(wlead), "step": P()}
    out = {"params": bufs(wlead), "opt": opt}
    if run_cfg.sync_quantize or run_cfg.outer_momentum > 0.0:
        out["anchor"] = bufs(alead)
        if run_cfg.outer_momentum > 0.0:
            out["outer_mu"] = bufs(alead)
    return out


def make_global(x, mesh, pspec, shape=None):
    """One host-replicated value -> a global array laid out on `mesh`.
    `make_array_from_callback` builds the buffer from its addressable shards
    only, so the same call works single-process (simulated devices) and
    across real `jax.distributed` processes — every process holds the
    identical host value, each contributes its own shards.  With `shape`,
    the value is broadcast to it first (a view: each shard is cut from the
    broadcast, which is never materialized).  Shared by RoundEngine init
    and the multihost harness so the two stay bitwise comparable."""
    from jax.sharding import NamedSharding
    xnp = np.asarray(x)
    if shape is not None:
        xnp = np.broadcast_to(xnp, shape)
    return jax.make_array_from_callback(xnp.shape, NamedSharding(mesh, pspec),
                                        lambda idx: xnp[idx])


# --------------------------------------------------------------------------
# Runtime-state conversion (the RoundEngine's layout="flat" entry points)
# --------------------------------------------------------------------------

_STACKED = ("m", "v", "mu")       # optimizer slots carrying the worker axis


def spec_for_params(params_single: Pytree) -> FlatParamSpace:
    return FlatParamSpace(params_single)


def to_flat_state(spec: FlatParamSpace, state: Pytree) -> Pytree:
    """Tree runtime state (local_update.init_state layout) -> flat state:
    params/opt moments become `{bucket: [W, N]}`, the sync anchor and outer
    momentum become `{bucket: [N]}`; scalars ride along unchanged."""
    out = {"params": spec.flatten(state["params"], lead=1)}
    out["opt"] = {k: (spec.flatten(v, lead=1) if k in _STACKED else v)
                  for k, v in state["opt"].items()}
    if "anchor" in state:
        out["anchor"] = spec.flatten(state["anchor"])
    if "outer_mu" in state:
        out["outer_mu"] = spec.flatten(state["outer_mu"])
    return out


def to_tree_state(spec: FlatParamSpace, state: Pytree) -> Pytree:
    """Inverse of `to_flat_state` (bitwise: slices of the concatenation)."""
    out = {"params": spec.unflatten(state["params"], lead=1)}
    out["opt"] = {k: (spec.unflatten(v, lead=1) if k in _STACKED else v)
                  for k, v in state["opt"].items()}
    if "anchor" in state:
        out["anchor"] = spec.unflatten(state["anchor"])
    if "outer_mu" in state:
        out["outer_mu"] = spec.unflatten(state["outer_mu"])
    return out
