"""Distributed gradient synchronization: the paper's techniques on a TPU mesh.

The federated "client" maps to a data-parallel worker group (one index along
the flattened (pod, data) mesh axes).  Per-group gradients are obtained with
``vmap(grad)`` over a leading group axis that is sharded across (pod, data) —
pure pjit/GSPMD, no replication-invariant tricks: XLA turns the mean over the
group axis into the all-reduce, and when the payload has been compressed to
int8 (qsgd) the all-reduce moves 4x fewer bytes — a *structural* saving
visible in the §Roofline collective term.  Sparsifying compressors (top-k)
keep dense carriers on-chip; their wire payloads are packed and *measured* by
the repro.comm codecs (bits_per_round below is a thin wrapper over that
ledger accounting), and additionally realized in frequency by hier/local
modes (bits * p).

Modes (SyncConfig.mode):
  dense  - mean over groups (baseline all-reduce; what FedAvg does per round)
  efbv   - EF-BV per-group compressed delta sync (Ch. 2): the gradient
           estimate used by the optimizer is h_bar + nu * mean_i C_i(g_i-h_i)
  ef21 / diana - parameter special cases of efbv
  hier   - Cohort-Squeeze (Ch. 5) on the fabric: dense intra-pod mean every
           step; inter-pod mean only every ``sync_period`` steps with the
           compressor applied to the pod-level delta (slow-link traffic
           drops by ~sync_period x payload ratio)
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SyncConfig
from repro.core import compressors as comp_lib
from repro.core.compressors import Compressor
from repro.obs.trace import annotate
from repro.utils.tree import tree_map


class SyncState(NamedTuple):
    """EF-BV state for the runtime: per-group control variates (leading group
    axis, sharded over (pod, data)) + replicated running average."""
    h: object        # pytree, leaves (G, *param_shape) float32
    h_bar: object    # pytree, leaves (*param_shape,) float32
    step: jax.Array


class TreeSyncState(NamedTuple):
    """Anchor cascade state for aggregation-tree sync (mode=hier + levels).

    ``anchors[l]`` is level l's anchor pytree, leaf-most level first: leaves
    carry a leading node axis of size n_parents(l), except the root (last
    level), whose anchor is unstacked — exactly ``SyncState.h_bar``'s shape,
    making the depth-1 cascade the classic hier state."""
    anchors: Tuple[object, ...]
    step: jax.Array


class CascadeLevel(NamedTuple):
    """Runtime spec of one cascade level (built from LevelConfig + tree)."""
    name: str
    compressor: Compressor
    lam: float
    period: int
    fanout: int


def make_sync_compressor(name: str, compress_ratio: float,
                         quant_bits: int) -> Compressor:
    """The registry mapping the runtime sync paths use (qsgd resolves to the
    sharded last-dim variant so 2D-sharded leaves stay unflattened)."""
    if name == "topk_block":
        return comp_lib.block_top_k(compress_ratio)
    if name == "rand_k":
        return comp_lib.rand_k(compress_ratio)
    if name == "top_k":
        return comp_lib.top_k(compress_ratio)
    if name == "qsgd":
        # runtime paths operate on sharded param/grad leaves: last-dim blocks
        return comp_lib.qsgd_sharded(quant_bits)
    if name == "identity":
        return comp_lib.identity()
    return comp_lib.make_compressor(name)


def build_compressor(sync: SyncConfig) -> Compressor:
    return make_sync_compressor(sync.compressor, sync.compress_ratio,
                                sync.quant_bits)


def build_cascade(sync: SyncConfig, tree=None) -> Tuple[CascadeLevel, ...]:
    """Resolve ``SyncConfig.levels`` against the (tree) topology preset.

    Level l's lambda comes from the compressor calculus (lambda_star) like
    the flat hier mode; fanouts come from the tree topology, paired by order.
    Periods must be nested (each a multiple of the level below) so that a
    level only syncs on steps where everything underneath it syncs too.
    """
    from repro.comm.tree import get_tree_topology

    if not sync.levels:
        raise ValueError("build_cascade needs SyncConfig.levels")
    if tree is None:
        tree = get_tree_topology(sync.topology)
    if len(sync.levels) != len(tree.levels):
        raise ValueError(
            f"SyncConfig.levels has {len(sync.levels)} levels but tree "
            f"topology {tree.name!r} has {len(tree.levels)}")
    out, prev = [], None
    for lc, tl in zip(sync.levels, tree.levels):
        c = make_sync_compressor(lc.compressor, lc.compress_ratio,
                                 lc.quant_bits)
        if lc.period < 1:
            raise ValueError(f"level {lc.name!r}: period must be >= 1")
        if prev is not None and lc.period % prev != 0:
            raise ValueError(
                f"level {lc.name!r}: period {lc.period} is not a multiple of "
                f"the level below ({prev}); cascade periods must be nested")
        lam = (comp_lib.lambda_star(c.eta, c.omega)
               if c.eta is not None and c.omega is not None else 1.0)
        out.append(CascadeLevel(lc.name or tl.name, c, lam, lc.period,
                                tl.fanout))
        prev = lc.period
    return tuple(out)


def sync_state_init(params, n_groups: int, sync: SyncConfig,
                    n_pods: int = 1) -> Optional[SyncState]:
    if sync.mode in ("dense",):
        return None
    if sync.mode == "hier":
        n_groups = n_pods  # control variates live at pod level
    zeros_g = tree_map(
        lambda p: jnp.zeros((n_groups,) + p.shape, jnp.float32), params)
    zeros = tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return SyncState(h=zeros_g, h_bar=zeros, step=jnp.zeros((), jnp.int32))


def sync_params(sync: SyncConfig, n_groups: int) -> Tuple[float, float]:
    """(lambda, nu) for the configured mode/compressor."""
    c = build_compressor(sync)
    if sync.mode in ("efbv", "ef21", "diana", "hier"):
        mode = "efbv" if sync.mode == "hier" else sync.mode
        return comp_lib.lambda_star(c.eta, c.omega), (
            comp_lib.nu_star(c.eta, comp_lib.omega_ran_independent(c.omega, n_groups))
            if mode == "efbv" and not c.deterministic
            else comp_lib.lambda_star(c.eta, c.omega)
            if mode in ("efbv", "ef21")
            else 1.0
        )
    return 1.0, 1.0


# ---------------------------------------------------------------------------
# Sync transforms on stacked per-group gradients (leading axis G)
# ---------------------------------------------------------------------------
def dense_sync(grads_g):
    """Plain mean over the group axis (XLA emits the all-reduce)."""
    return tree_map(lambda g: jnp.mean(g, axis=0), grads_g)


def efbv_sync(key, grads_g, state: SyncState, c: Compressor, lam: float,
              nu: float, bucket_size: Optional[int] = None):
    """EF-BV over stacked per-group grads. Returns (g_est, new_state).

    By default the pytree is fused into fixed-size fp32 buckets
    (repro.comm.buckets) so the whole tree is compressed in ONE vmapped
    call per group instead of a per-leaf Python loop of small kernels —
    top-k/rand-k then select over the full gradient vector (the paper's
    d-dimensional operator) rather than per leaf.  ``bucket_size=0`` keeps
    the legacy per-leaf path (per-leaf compressor semantics).

    Sharding-safe compressors (``flatten=False``, e.g. qsgd_sharded) always
    take the per-leaf path: bucketize's reshape/concat is exactly the
    flatten that forces GSPMD to all-gather 2D-sharded leaves, the thing
    those compressors exist to avoid.
    """
    from repro.comm import buckets as bk

    if bucket_size is None:
        bucket_size = bk.DEFAULT_BUCKET_SIZE
    if not bucket_size or not c.flatten:
        with annotate("sync/efbv"):
            return _efbv_sync_leaves(key, grads_g, state, c, lam, nu)
    with annotate("sync/efbv"):
        with annotate("sync/bucketize"):
            g_b, layout = bk.bucketize_groups(grads_g, bucket_size)  # (G, nb, B)
            h_b, _ = bk.bucketize_groups(state.h, bucket_size)
            hb_b, _ = bk.bucketize(state.h_bar, bucket_size)         # (nb, B)
        keys = jax.random.split(key, g_b.shape[0])
        with annotate("sync/compress"):
            d_i = _fused_compress(c, keys, g_b - h_b, layout.d)
        d = jnp.mean(d_i, axis=0)
        f32 = jnp.float32
        with annotate("sync/debucketize"):
            return (
                bk.debucketize(hb_b + nu * d, layout, dtype=f32),
                SyncState(h=bk.debucketize_groups(h_b + lam * d_i, layout,
                                                  dtype=f32),
                          h_bar=bk.debucketize(hb_b + lam * d, layout,
                                               dtype=f32),
                          step=state.step + 1),
            )


def fused_apply(fn, delta_b, d: int):
    """Apply ``fn`` to the true d-dim rows of a bucketed (G, nb, B) delta.

    ``fn`` maps a ``(G, d)`` matrix to a ``(G, d)`` matrix; the zero-padded
    bucket tail is stripped before and restored after, so size-dependent
    operators (top-k's k, rand-k's d/k scale) see the real dimension.  This
    is the reshape/pad contract every fused compression pass shares — the
    cohort engine's per-class leaf compression plugs in through it.
    """
    G = delta_b.shape[0]
    flat = delta_b.reshape(G, -1)
    pad = flat.shape[1] - d
    out = fn(flat[:, :d])
    if pad:
        out = jnp.pad(out, ((0, 0), (0, pad)))
    return out.reshape(delta_b.shape)


def _map_groups(c: Compressor, keys, x):
    """``c`` vmapped over the leading worker-group axis of ``x``.  Traced
    under a mesh (``jax.set_mesh``), a Pallas compressor runs inside a
    shard_map over the mesh's data axes, each device on its own groups:
    GSPMD cannot partition a Mosaic kernel."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not c.pallas:
        return jax.vmap(c)(keys, x)
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import data_axes

    spec = P(data_axes(mesh))
    return jax.shard_map(jax.vmap(c), in_specs=(spec, spec), out_specs=spec,
                         check_vma=False)(keys, x)


def _fused_compress(c: Compressor, keys, delta_b, d: int):
    """One fused compressor pass over the bucketed (G, n_buckets, B) delta.

    The compressor must see the TRUE d-dim vector, not the padded bucket
    matrix: top-k/rand-k derive k (and rand-k its d/k scale) from the input
    size, so compressing the zero-padded tail would inflate k for trees
    smaller than a bucket.  (Only ``flatten=True`` compressors reach this —
    sharding-safe ones stay on the per-leaf path.)
    """
    return fused_apply(
        lambda core: _map_groups(c, keys, core), delta_b, d)


def _efbv_sync_leaves(key, grads_g, state: SyncState, c: Compressor,
                      lam: float, nu: float):
    """Per-leaf EF-BV (one compressor kernel per pytree leaf)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads_g)
    h_leaves = treedef.flatten_up_to(state.h)
    hb_leaves = treedef.flatten_up_to(state.h_bar)
    G = leaves[0].shape[0]

    g_est, new_h, new_hb = [], [], []
    for li, (g, h, hb) in enumerate(zip(leaves, h_leaves, hb_leaves)):
        lkey = jax.random.fold_in(key, li)
        keys = jax.random.split(lkey, G)
        delta = g.astype(jnp.float32) - h
        d_i = _map_groups(c, keys, delta)
        d = jnp.mean(d_i, axis=0)
        new_h.append(h + lam * d_i)
        g_est.append(hb + nu * d)
        new_hb.append(hb + lam * d)
    unf = jax.tree_util.tree_unflatten
    return (
        unf(treedef, g_est),
        SyncState(h=unf(treedef, new_h), h_bar=unf(treedef, new_hb),
                  step=state.step + 1),
    )


def tree_sync_state_init(params, levels: Sequence[CascadeLevel]) -> TreeSyncState:
    """Anchors for every cascade level, all seeded from the shared params."""
    n = 1
    for lev in levels:
        n *= lev.fanout
    anchors = []
    for l, lev in enumerate(levels):
        n //= lev.fanout
        if l == len(levels) - 1:
            anchors.append(tree_map(lambda p: p.astype(jnp.float32), params))
        else:
            anchors.append(tree_map(
                lambda p, n=n: jnp.broadcast_to(
                    p.astype(jnp.float32)[None], (n,) + p.shape), params))
    return TreeSyncState(anchors=tuple(anchors), step=jnp.zeros((), jnp.int32))


def _level_key(key, l: int, n_levels: int):
    """Per-level PRNG key, stable under added depth: keyed by distance from
    the root so the top (inter) level of any cascade draws the same noise as
    the classic single-level ``hier_param_sync``."""
    dist = n_levels - 1 - l
    return key if dist == 0 else jax.random.fold_in(key, dist)


def _survivor_masks(survivors, levels):
    """Normalize per-level survivor masks (None = everyone made the round).

    ``survivors[l]`` masks level l's *children* (the training leaves for
    l=0, the level-(l-1) aggregators above that); entries > 0 participated.
    Returns a list of float32 arrays or Nones, one per level.
    """
    if survivors is None:
        return [None] * len(levels)
    survivors = tuple(survivors)
    if len(survivors) != len(levels):
        raise ValueError(f"{len(survivors)} survivor masks for "
                         f"{len(levels)} cascade levels")
    n = 1
    for lev in levels:
        n *= lev.fanout
    out = []
    for l, (m, lev) in enumerate(zip(survivors, levels)):
        if m is None:
            out.append(None)
        else:
            m = jnp.asarray(m, jnp.float32)
            if m.shape != (n,):
                raise ValueError(
                    f"level {lev.name!r}: survivor mask shape {m.shape}, "
                    f"expected ({n},)")
            out.append(m)
        n //= lev.fanout
    return out


def _survivor_weights(m, f: int):
    """Mean-preserving reweighting for a masked mean over ``f`` children.

    ``jnp.mean(d * w)`` over the child axis equals the mean over survivors
    only: ``w = m * (f / max(sum(m), 1))``.  With an all-ones mask ``w`` is
    *exactly* 1.0 (f/f), so the weighted mean lowers to the identical XLA op
    as the unmasked one — the bit-identity guarantee the zero-fault path
    rides on.  A group with zero survivors gets w == 0 everywhere: its
    anchor takes no step this round (EF21 state carried, not corrupted).
    """
    if m.ndim == 1:
        return m * (f / jnp.maximum(jnp.sum(m), 1.0))
    return m * (f / jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0))


def tree_param_sync(key, params_g, state: TreeSyncState,
                    levels: Sequence[CascadeLevel],
                    bucket_size: Optional[int] = None,
                    survivors=None, leaf_compress=None):
    """Multi-level anchor cascade (Cohort-Squeeze beyond two levels).

    params_g: pytree with leading leaf axis G = prod(fanout_l) — one training
    replica per tree leaf.  Level l (leaf-most first) keeps one anchor per
    aggregator node; every ``period[l]`` steps its children (the leaves for
    l=0, the level-(l-1) anchors above that) sync through a compressed EF21
    delta against their parent anchor:

        d_i        = C_l(child_i - anchor_parent)
        anchor    += lam_l * mean_i d_i
        child_i   <- anchor            (the whole subtree adopts — see below)

    Periods are nested (validated by ``build_cascade``): a level only syncs
    on steps where every level below it also syncs, so one bottom-up pass
    folds fresh leaf progress into each anchor before it is pushed upward,
    and a final top-down pass makes every node below the highest synced
    level adopt that ancestor's new anchor.  The depth-1 cascade is exactly
    the classic ``hier_param_sync`` (which now wraps this), and a depth-2
    [intra=identity/period 1, inter=C/period p] cascade reproduces it on the
    per-pod means bit-for-bit.

    Like ``efbv_sync`` the tree is bucket-fused by default; ``bucket_size=0``
    or any sharding-safe ``flatten=False`` level compressor selects the
    per-leaf path.  Returns (new params_g, new TreeSyncState).

    ``survivors`` (optional, from ``FaultModel.round_plan``) is one mask per
    level over that level's children; non-survivors are excluded from the
    anchor update via a mean-preserving reweighting (``_survivor_weights``)
    and dropped *leaves* skip the top-down adoption — they keep their local
    params and re-anchor on their next surviving round, so their EF21 state
    is carried, never corrupted.  ``survivors=None`` (or all-ones masks) is
    bit-identical to the faultless path; the aggregator down-path is modeled
    reliable, so inner anchors always adopt.

    ``leaf_compress`` (optional) replaces level 0's fused compressor pass
    with a custom ``(keys, delta_b, d) -> d_i`` callable (same bucketed
    shapes — build it on ``fused_apply``).  The cohort engine uses this to
    compress each leaf's delta with its *own link class's* compressor while
    the rest of the cascade runs unchanged.  Fused path only: heterogeneous
    per-leaf compression over a stacked dense cohort has no per-leaf
    (sharding-safe) analogue.
    """
    from repro.comm import buckets as bk

    if bucket_size is None:
        bucket_size = bk.DEFAULT_BUCKET_SIZE
    levels = tuple(levels)
    prev = None
    for lev in levels:
        if prev is not None and lev.period % prev != 0:
            raise ValueError(
                f"level {lev.name!r}: period {lev.period} not a multiple of "
                f"the level below ({prev}); cascade periods must be nested")
        prev = lev.period
    G = jax.tree_util.tree_leaves(params_g)[0].shape[0]
    n_expected = 1
    for lev in levels:
        n_expected *= lev.fanout
    if G != n_expected:
        raise ValueError(f"params_g has {G} leaves but cascade fanouts "
                         f"multiply to {n_expected}")

    # nested periods: the number of levels syncing this step fully describes
    # the round (level l syncs => every level below does too)
    n_sync = jnp.zeros((), jnp.int32)
    for lev in levels:
        n_sync = n_sync + ((state.step % lev.period)
                           == (lev.period - 1)).astype(jnp.int32)

    fused = bool(bucket_size) and all(lev.compressor.flatten for lev in levels)
    if leaf_compress is not None and not fused:
        raise ValueError(
            "leaf_compress requires the fused (bucketized) path: set a "
            "bucket_size > 0 and use flatten=True level compressors")
    masks = _survivor_masks(survivors, levels)

    # gate the whole sync (including the fused path's bucketize/debucketize
    # round-trip) behind the step test, so off-period steps stay free like
    # the old single-level lax.cond did
    def do_sync(args):
        params_g, anchors, n_sync = args
        st = TreeSyncState(anchors=anchors, step=state.step)
        if fused:
            return _tree_sync_fused(key, params_g, st, levels, bucket_size,
                                    n_sync, masks, leaf_compress)
        return _tree_sync_leaves(key, params_g, st, levels, n_sync, masks)

    def no_sync(args):
        params_g, anchors, _ = args
        return params_g, anchors

    new_p, new_anchors = jax.lax.cond(
        n_sync > 0, do_sync, no_sync, (params_g, state.anchors, n_sync))
    return new_p, TreeSyncState(anchors=new_anchors, step=state.step + 1)


def _tree_sync_fused(key, params_g, state, levels, bucket_size, n_sync,
                     masks=None, leaf_compress=None):
    from repro.comm import buckets as bk

    L = len(levels)
    masks = masks or [None] * L
    p_b, layout = bk.bucketize_groups(params_g, bucket_size)     # (G, nb, B)
    G = p_b.shape[0]
    anchors_b = []
    for l in range(L):
        if l == L - 1:
            a_b, _ = bk.bucketize(state.anchors[l], bucket_size)  # (nb, B)
        else:
            a_b, _ = bk.bucketize_groups(state.anchors[l], bucket_size)
        anchors_b.append(a_b)

    def compress(l, keys, delta_b):
        if l == 0 and leaf_compress is not None:
            return leaf_compress(keys, delta_b, layout.d)
        return _fused_compress(levels[l].compressor, keys, delta_b, layout.d)

    def level_sync(l, child_b, parent_b):
        lev = levels[l]
        m = masks[l]
        with annotate(f"sync/level/{lev.name}"):
            keys = jax.random.split(_level_key(key, l, L), child_b.shape[0])
            if parent_b.ndim == 2:                  # root: unstacked anchor
                d_i = compress(l, keys, child_b - parent_b)
                if m is not None:
                    d_i = d_i * _survivor_weights(m, d_i.shape[0])[:, None, None]
                return parent_b + lev.lam * jnp.mean(d_i, axis=0)
            n_par = parent_b.shape[0]
            f = child_b.shape[0] // n_par
            d_i = compress(l, keys,
                           child_b - jnp.repeat(parent_b, f, axis=0))
            d_g = d_i.reshape((n_par, f) + d_i.shape[1:])
            if m is not None:
                w = _survivor_weights(m.reshape(n_par, f), f)
                d_g = d_g * w[:, :, None, None]
            return parent_b + lev.lam * jnp.mean(d_g, axis=1)

    def make_branch(j):
        def branch(args):
            p_b, anchors = args
            anchors = list(anchors)
            child = p_b
            for l in range(j):
                anchors[l] = level_sync(l, child, anchors[l])
                child = anchors[l] if anchors[l].ndim == 3 else anchors[l][None]
            if j:
                top = anchors[j - 1]
                top_s = top if top.ndim == 3 else top[None]
                for l in range(j - 1):
                    reps = anchors[l].shape[0] // top_s.shape[0]
                    adopted = jnp.repeat(top_s, reps, axis=0)
                    if masks[l + 1] is not None:
                        # groups whose uplink was dead carry their EF21
                        # anchor instead of adopting the ancestor
                        adopted = jnp.where(masks[l + 1][:, None, None] > 0,
                                            adopted, anchors[l])
                    anchors[l] = adopted
                p_out = jnp.repeat(top_s, G // top_s.shape[0], axis=0)
                if masks[0] is not None:
                    # dropped leaves keep their local params this round
                    p_out = jnp.where(masks[0][:, None, None] > 0, p_out, p_b)
            else:
                p_out = p_b
            return p_out, tuple(anchors)
        return branch

    p_out, anchors_out = jax.lax.switch(
        n_sync, [make_branch(j) for j in range(L + 1)], (p_b, tuple(anchors_b)))
    new_anchors = tuple(
        bk.debucketize(anchors_out[l], layout, dtype=jnp.float32)
        if l == L - 1 else
        bk.debucketize_groups(anchors_out[l], layout, dtype=jnp.float32)
        for l in range(L))
    return bk.debucketize_groups(p_out, layout), new_anchors


def _tree_sync_leaves(key, params_g, state, levels, n_sync, masks=None):
    """Per-leaf cascade (one compressor kernel per pytree leaf per level)."""
    L = len(levels)
    masks = masks or [None] * L
    leaves, treedef = jax.tree_util.tree_flatten(params_g)
    anchors_lv = [tuple(treedef.flatten_up_to(a)) for a in state.anchors]

    def _wcol(w, ndim):
        return w.reshape(w.shape + (1,) * (ndim - w.ndim))

    def level_sync(l, li, child, parent):
        lev = levels[l]
        m = masks[l]
        with annotate(f"sync/level/{lev.name}"):
            keys = jax.random.split(
                jax.random.fold_in(_level_key(key, l, L), li), child.shape[0])
            delta = child.astype(jnp.float32)
            if parent.ndim == child.ndim:           # stacked (non-root) anchor
                n_par = parent.shape[0]
                f = child.shape[0] // n_par
                delta = delta - jnp.repeat(parent, f, axis=0)
                d_i = jax.vmap(lambda k, v: lev.compressor(k, v))(keys, delta)
                d_g = d_i.reshape((n_par, f) + d_i.shape[1:])
                if m is not None:
                    w = _survivor_weights(m.reshape(n_par, f), f)
                    d_g = d_g * _wcol(w, d_g.ndim)
                return parent + lev.lam * jnp.mean(d_g, axis=1)
            d_i = jax.vmap(lambda k, v: lev.compressor(k, v))(keys,
                                                              delta - parent)
            if m is not None:
                d_i = d_i * _wcol(_survivor_weights(m, d_i.shape[0]),
                                  d_i.ndim)
            return parent + lev.lam * jnp.mean(d_i, axis=0)

    def make_branch(j):
        def branch(args):
            leaves, anchors = args
            anchors = [list(a) for a in anchors]
            new_leaves = list(leaves)
            for li, p in enumerate(leaves):
                child = p
                for l in range(j):
                    anchors[l][li] = level_sync(l, li, child, anchors[l][li])
                    a = anchors[l][li]
                    child = a if a.ndim == p.ndim else a[None]
                if j:
                    top = anchors[j - 1][li]
                    top_s = top if top.ndim == p.ndim else top[None]
                    for l in range(j - 1):
                        reps = anchors[l][li].shape[0] // top_s.shape[0]
                        adopted_a = jnp.repeat(top_s, reps, axis=0)
                        if masks[l + 1] is not None:
                            # dead-uplink groups carry their EF21 anchor
                            adopted_a = jnp.where(
                                _wcol(masks[l + 1], adopted_a.ndim) > 0,
                                adopted_a, anchors[l][li])
                        anchors[l][li] = adopted_a
                    adopted = jnp.repeat(
                        top_s.astype(p.dtype), p.shape[0] // top_s.shape[0],
                        axis=0) if top_s.shape[0] > 1 else jnp.broadcast_to(
                            top_s[0].astype(p.dtype)[None], p.shape)
                    if masks[0] is not None:
                        # dropped leaves keep their local params this round
                        adopted = jnp.where(
                            _wcol(masks[0], p.ndim) > 0, adopted, p)
                    new_leaves[li] = adopted
            return tuple(new_leaves), tuple(tuple(a) for a in anchors)
        return branch

    leaves_out, anchors_out = jax.lax.switch(
        n_sync, [make_branch(j) for j in range(L + 1)],
        (tuple(leaves), tuple(anchors_lv)))
    unf = jax.tree_util.tree_unflatten
    new_anchors = tuple(unf(treedef, list(a)) for a in anchors_out)
    return unf(treedef, list(leaves_out)), new_anchors


def hier_param_sync(key, params_g, state: SyncState, c: Compressor, lam: float,
                    period: int, bucket_size: Optional[int] = None,
                    survivors=None):
    """Cohort-Squeeze / local training on the fabric (param-level EF21 sync).

    params_g: pytree with leading group axis (pods, or (pod x data) worker
    groups for 'local' mode), each group training locally between syncs with
    its own optimizer.  Every ``period`` steps, groups sync through an EF21
    compressed delta against the shared anchor h_bar:

        d_i    = C_i(params_i - h_bar)
        h_bar += lam * mean_i d_i
        params_i <- h_bar                      (everyone adopts the anchor)

    With identity compressor and lam=1 this is exact parameter averaging
    (FedAvg); with top-k/qsgd the inter-group traffic carries only the
    compressed delta.  Returns (new params_g, new state).

    This is the depth-1 special case of ``tree_param_sync`` — one cascade
    level whose fanout is the whole group axis.  Like ``efbv_sync``, the
    parameter tree is bucket-fused by default (``bucket_size=0`` restores the
    per-leaf loop, and sharding-safe ``flatten=False`` compressors always
    take it — see ``efbv_sync``).
    """
    G = jax.tree_util.tree_leaves(params_g)[0].shape[0]
    lev = CascadeLevel("inter", c, lam, int(period), G)
    tstate = TreeSyncState(anchors=(state.h_bar,), step=state.step)
    if survivors is not None and not isinstance(survivors, (tuple, list)):
        survivors = (survivors,)  # single group-axis mask
    new_p, ts = tree_param_sync(key, params_g, tstate, (lev,),
                                bucket_size=bucket_size, survivors=survivors)
    return new_p, SyncState(h=state.h, h_bar=ts.anchors[0], step=ts.step)


# ---------------------------------------------------------------------------
# Bits accounting (per communication round, per worker) — the paper's metric
# ---------------------------------------------------------------------------
def bits_per_round(sync: SyncConfig, n_params: int) -> float:
    """Thin wrapper over repro.comm accounting.

    The number is *measured*: the configured compressor's codec encodes a
    probe payload and the packed-buffer bytes are amortized per mode/period
    (see repro.comm.accounting.round_cost).  The old closed-form model lives
    on as RoundCost.analytic_bits, used only as a cross-check.
    """
    from repro.comm import round_bits

    return round_bits(sync, n_params)


def round_comm(sync: SyncConfig, n_params: int, topology=None):
    """Full per-round communication report (bytes per link class + simulated
    wall-clock on the configured link topology). Convenience re-export so the
    runtime sync modes and the launch costing share one accounting path."""
    from repro.comm import round_cost

    return round_cost(sync, n_params, topology=topology)
