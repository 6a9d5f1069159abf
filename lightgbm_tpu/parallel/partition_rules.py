"""ONE partition-rule layer for every mesh learner.

Reference analog: the reference's distributed modes each hand-roll
their placement (``src/treelearner/*_parallel_tree_learner.cpp`` each
decide what is replicated, row-split or column-split inline). Here the
placement of every named training array is a DECLARATIVE TABLE —
regex name-pattern -> ``PartitionSpec`` resolved against one
``jax.sharding.Mesh`` (the pattern of SNIPPETS [2]/[3]: partition
rules -> sharding specs -> shard/gather helpers) — and the four mesh
learners (data / feature / voting / mesh-partitioned) are each a SPEC
TABLE plus a comm recipe (``learner/comm.py``) over the same grow
program, not a bespoke class body.

The layer owns three things:

* **mesh construction** — ``default_mesh`` / ``mesh_from_config``
  (the ``num_machines`` resolution of config.h:866);
* **spec resolution** — ``MODE_RULES[mode]`` maps array NAMES to
  ``PartitionSpec``s; ``spec_for`` pads a rule's spec with ``None`` up
  to the array's rank, so one rule covers ``grad [N]`` and
  ``binned [N, G]`` alike; ``shard_map`` in/out specs and
  ``device_put`` shardings both come from the same table;
* **feature-shard planning** — ``plan_feature_shards`` computes the
  balanced group->shard assignment and the permuted per-shard
  ``FeatureMeta`` that BOTH column-sharded scan layouts consume: the
  feature-parallel learner (histogram build itself sharded) and the
  data-parallel reduce-scatter recipe (histograms built locally over
  all groups, then reduce-scattered so each shard scans its slice of
  the globally-reduced histogram — the reference's
  ``ReduceScatter`` shape, data_parallel_tree_learner.cpp:149-164).

EFB bundles shard as whole GROUPS (a bundle's features must stay
together — its group histogram debundles locally); groups are assigned
largest-first to the least-loaded shard and the per-shard scan axis is
a permuted/padded feature list whose ``meta.group`` holds LOCAL column
indices and whose ``meta.global_id`` maps winners back to global
feature ids.
"""

from __future__ import annotations

import re
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..ops.split import FeatureMeta

AXIS = "data"  # single mesh axis; rows or features are sharded over it


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the
    grow bodies replicate split choices by construction (collectives
    in learner/comm.py), which the checker cannot see through the
    Pallas calls."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def default_mesh(num_devices: Optional[int] = None) -> Mesh:
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            # the reference treats a machine-count mismatch as fatal
            # (linkers_socket.cpp), and a silently smaller mesh trains
            # a different job than the one asked for
            from ..utils.log import LightGBMError
            raise LightGBMError(
                f"num_machines={num_devices} but only {len(devices)} "
                "device(s) are visible")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (AXIS,))


def mesh_from_config(config: Config) -> Mesh:
    """Resolve the shard count the way the reference resolves
    num_machines (config.h:866): an explicit num_machines > 1 or
    n_devices > 0 caps the mesh; otherwise every visible device joins."""
    if config.num_machines > 1:
        return default_mesh(config.num_machines)
    if config.n_devices > 0:
        return default_mesh(config.n_devices)
    return default_mesh()


def mesh_shards(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


# ---------------------------------------------------------------------
# partition rules: regex name-pattern -> PartitionSpec, per mode.
# A rule's spec is padded with None up to each array's rank, so
# P(AXIS) covers grad [N] and binned [N, G] alike; P() is replicated
# at any rank. First match wins; every table ends with a catch-all.
_ROW_SHARDED = (r"^(binned|mv_slots|grad|hess|bag_weight|leaf_id"
                r"|mat|ws)$")
_SHARD_LOCAL = r"^(meta_local|fmask_local)"

MODE_RULES: Dict[str, Tuple[Tuple[str, P], ...]] = {
    # rows sharded; scan axis sharded via the reduce-scattered
    # histogram slice (meta_local); split choice replicated
    "data": (
        (_ROW_SHARDED, P(AXIS)),
        (_SHARD_LOCAL, P(AXIS)),
        (r".*", P()),
    ),
    # rows replicated; histogram-build columns and the scan axis
    # sharded; split choice replicated via the winner gather
    "feature": (
        (r"^binned_hist$", P(None, AXIS)),
        (_SHARD_LOCAL, P(AXIS)),
        (r".*", P()),
    ),
    # rows sharded; local scans over the FULL feature axis; only the
    # voted winners' histogram columns are aggregated
    "voting": (
        (_ROW_SHARDED, P(AXIS)),
        (r".*", P()),
    ),
}
# the mesh-partitioned learners reuse the data/voting tables (their
# segment matrices mat/ws are row-sharded like binned)
MODE_RULES["partitioned-data"] = MODE_RULES["data"]
MODE_RULES["partitioned-voting"] = MODE_RULES["voting"]


def spec_for(mode: str, name: str, ndim: int = 1) -> P:
    """The partition spec of array ``name`` in ``mode``, padded with
    ``None`` up to ``ndim``."""
    for pattern, spec in MODE_RULES[mode]:
        if re.search(pattern, name) is not None:
            if not len(spec):
                return spec          # replicated at any rank
            pad = ndim - len(spec)
            return P(*spec, *([None] * pad)) if pad > 0 else spec
    raise KeyError(f"no partition rule for {name!r} in mode {mode!r}")


def in_specs_for(mode: str, named: Dict[str, int]) -> Tuple[P, ...]:
    """shard_map ``in_specs`` for an ordered ``{name: ndim}`` mapping
    (python dicts preserve insertion order)."""
    return tuple(spec_for(mode, n, d) for n, d in named.items())


def sharding_for(mesh: Mesh, mode: str, name: str,
                 ndim: int = 1) -> NamedSharding:
    return NamedSharding(mesh, spec_for(mode, name, ndim))


def shard_arrays(mesh: Mesh, mode: str, arrays: Dict[str, object]
                 ) -> Dict[str, object]:
    """device_put every named array with its rule's sharding (host
    numpy in -> per-shard transfers, no replicated staging copy —
    see parallel/ingest.py for the row-sharded fast path)."""
    out = {}
    for name, arr in arrays.items():
        ndim = int(np.ndim(arr)) or 1
        leaves = jax.tree.leaves(arr)
        if leaves and hasattr(leaves[0], "ndim"):
            ndim = leaves[0].ndim
        sh = sharding_for(mesh, mode, name, ndim)
        out[name] = jax.tree.map(lambda a: jax.device_put(a, sh), arr)
    return out


# ---------------------------------------------------------------------
# feature-shard planning: ONE balanced group->shard assignment consumed
# by every column-sharded scan layout (feature-parallel's sharded
# histogram build AND data-parallel's reduce-scattered histogram).
class FeatureShardPlan(NamedTuple):
    """Static (host) plan of the column-sharded scan axis."""
    d: int                 # shard count
    f_local: int           # feature slots per shard
    f_pad: int             # d * f_local (padded scan axis)
    g_local: int           # group slots per shard
    g_pad: int             # d * g_local (padded histogram axis)
    meta_local: FeatureMeta  # [f_pad] permuted meta; .group = LOCAL
    #                          column index, .global_id -> global id
    col_perm: np.ndarray   # [g_pad] int64 global group of each slot
    col_live: np.ndarray   # [g_pad] bool live slots
    feat_perm: np.ndarray  # [f_pad] int64 global feature (-1 = pad)
    feat_col: np.ndarray   # [f_pad] int32 LOCAL column of each slot

    def shard_meta(self, meta: FeatureMeta, idx) -> FeatureMeta:
        """Shard ``idx``'s ``[f_local]`` slice of ``meta_local``,
        computed from a (possibly traced) global ``meta``: the
        permutation is the plan's, the values are the table's, so a
        compiled program that takes ``meta`` as an argument holds
        nothing of the table (docs/ARCHITECTURE.md, "What a compiled
        program may hold")."""
        full = _permute_meta(meta, self.feat_perm, self.feat_col,
                             meta.num_bins.shape[0])
        return FeatureMeta(*(jax.lax.dynamic_slice_in_dim(
            a, idx * self.f_local, self.f_local) for a in full))

    def permute_hist(self, hist: jnp.ndarray) -> jnp.ndarray:
        """[G, B, 3] group histogram -> [g_pad, B, 3] in shard-slice
        order (dead slots zero) — the reduce-scatter input layout."""
        safe = jnp.asarray(np.where(self.col_live, self.col_perm, 0))
        live = jnp.asarray(self.col_live)
        return jnp.where(live[:, None, None], hist[safe],
                         jnp.zeros((), hist.dtype))

    def permute_binned(self, binned: np.ndarray) -> np.ndarray:
        """[N, G] host matrix -> [N, g_pad] column-permuted copy (dead
        columns zero) — feature-parallel's sharded histogram input."""
        safe = np.where(self.col_live, self.col_perm, 0)
        return np.where(self.col_live[None, :], binned[:, safe],
                        np.zeros((), binned.dtype))


def _permute_meta(meta: FeatureMeta, perm: np.ndarray,
                  feat_col: np.ndarray, f: int) -> FeatureMeta:
    """Permuted/padded per-shard scan meta: ``perm`` lists the global
    feature of each scan slot (-1 = never-splittable padding),
    ``feat_col`` its LOCAL column. Traceable: ``meta`` may be a traced
    argument; the permutation is static."""
    live = perm >= 0
    safe = np.where(live, perm, 0)

    def take(arr, pad_value, dtype=None):
        a = jnp.asarray(arr)
        out = jnp.where(live, a[safe], pad_value)
        return out if dtype is None else out.astype(dtype)

    return FeatureMeta(
        num_bins=take(meta.num_bins, 2),
        missing=take(meta.missing, 0),
        default_bin=take(meta.default_bin, 0),
        most_freq_bin=take(meta.most_freq_bin, 0),
        monotone=take(meta.monotone, 0),
        penalty=take(meta.penalty, 1.0, np.float32),
        is_categorical=take(meta.is_categorical, False),
        # LOCAL column index inside the shard's histogram slice
        group=jnp.asarray(feat_col),
        offset=take(meta.offset, 0),
        cegb_coupled_penalty=take(meta.cegb_coupled_penalty, 0.0,
                                  np.float32),
        cegb_lazy_penalty=take(meta.cegb_lazy_penalty, 0.0,
                               np.float32),
        global_id=jnp.asarray(
            np.where(live, perm, f).astype(np.int32)))


def plan_feature_shards(meta: FeatureMeta, num_features: int,
                        num_groups: int, d: int) -> FeatureShardPlan:
    """Balanced group->shard assignment + the permuted per-shard scan
    meta. Groups (EFB bundles; 1:1 with features on unbundled data;
    multi-val pseudo-groups included) are assigned largest-first to
    the least-loaded shard by FEATURE count; each shard's features are
    sorted ascending by global id so serial's first-index tie-break is
    preserved within the shard (the winner gather breaks cross-shard
    ties by lower global id — learner/comm.py)."""
    groups = np.asarray(meta.group)                   # [F] global
    feat_of_group = [np.where(groups == g)[0] for g in range(num_groups)]
    order = np.argsort([-len(fg) for fg in feat_of_group],
                       kind="stable")
    shard_groups: list = [[] for _ in range(d)]
    load = [0] * d
    for g in order:
        s = min(range(d), key=lambda i: (load[i], i))
        shard_groups[s].append(int(g))
        load[s] += len(feat_of_group[int(g)])
    g_local = max(1, max(len(sg) for sg in shard_groups))
    f_local = max(1, max(load))
    g_pad, f_pad = d * g_local, d * f_local
    col_perm = np.zeros(g_pad, np.int64)
    col_live = np.zeros(g_pad, bool)
    local_col_of_group = np.zeros(max(num_groups, 1), np.int32)
    for s, sg in enumerate(shard_groups):
        for j, g in enumerate(sg):
            col_perm[s * g_local + j] = g
            col_live[s * g_local + j] = True
            local_col_of_group[g] = j
    perm = np.full(f_pad, -1, np.int64)
    for s, sg in enumerate(shard_groups):
        fl = np.sort(np.concatenate(
            [feat_of_group[g] for g in sg]).astype(np.int64)) \
            if sg else np.zeros(0, np.int64)
        perm[s * f_local:s * f_local + len(fl)] = fl
    feat_col = np.where(perm >= 0, local_col_of_group[groups][
        np.maximum(perm, 0)], 0).astype(np.int32)
    meta_local = _permute_meta(meta, perm, feat_col, num_features)
    return FeatureShardPlan(d=d, f_local=f_local, f_pad=f_pad,
                            g_local=g_local, g_pad=g_pad,
                            meta_local=meta_local, col_perm=col_perm,
                            col_live=col_live, feat_perm=perm,
                            feat_col=feat_col)


def local_feature_mask(meta_local: FeatureMeta, feature_mask,
                       num_features: int):
    """The shard's slice of a replicated [F] feature mask, gathered
    through the permuted scan meta (traceable — runs inside the
    shard_map body so the replicated mask never needs a host-side
    permutation)."""
    gid = meta_local.global_id
    live = gid < num_features
    return live & feature_mask[jnp.clip(gid, 0, num_features - 1)]


def split_bynode_budget(count: int, d: int) -> Tuple[int, int, int]:
    """Per-shard slice of the global by-node feature budget:
    floor(count/d) per shard plus one for the first count%d shards —
    the total matches the configured count. Returns
    (floor, remainder, static per-shard cap)."""
    floor, rem = divmod(int(count), d)
    return floor, rem, floor + (1 if rem else 0)
