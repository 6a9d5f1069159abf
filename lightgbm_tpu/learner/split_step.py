"""The split step of the fused grow loops: which one runs
(``plan_split_step``) and the packed carry it works on (``StatePack``).
What one split scans and writes, the definitions the grow bodies share
with the megakernel, is below both, in ``ops/split.py``.

The serial (``learner/serial.py``) and partitioned
(``learner/partitioned.py``) learners compile the whole
``num_leaves - 1`` grow loop into ONE ``lax.while_loop`` program; what
this module owns is the per-split *dispatch economy* inside that
program — the reference wins its grow loop by doing almost nothing per
split beyond one smaller-child histogram plus a subtraction
(``serial_tree_learner.cpp:434-436``), and the XLA analog of "almost
nothing" is a while-loop body that lowers to as few executable ops as
possible (measured by ``tools/hlo_census.py`` against a committed
budget).

Two packing modes, selected per trace by the learners (the
``LGBM_TPU_SPLIT_FUSION`` env var, default on):

* **fused** (``merged=True``) — all float per-leaf state rides ONE
  ``[Kf + Ki, L]`` f32 matrix (int rows bitcast to f32, value bits
  preserved exactly); the tree arrays ride one ``[Ktf + Kti, L-1]``
  matrix. Each split then costs ONE two-column scatter for the leaf
  state, ONE column write + ONE two-row fixup for the tree arrays, and
  ONE column slice for the split-site read. Rows that are derivable
  (``leaf_weight`` == ``leaf_h``, ``leaf_count`` == ``leaf_c``,
  ``leaf_parent`` == ``ref_node``), constant under the config
  (monotone bounds without monotone constraints) or dead (categorical
  bitsets on numerical-only datasets) are dropped from the carry and
  synthesized by ``view()`` — the slim-carry half of the round-6
  directive.

* **legacy** (``merged=False``) — the r05 layout: split SF/SI/TF/TI
  matrices, full field set, per-field column writes. Kept as the
  bit-exactness foil: ``tests/test_split_fusion.py`` trains both modes
  and asserts byte-identical models.

Both modes store and read the SAME values, so every model is
bit-identical across modes by construction; the test suite enforces it
across bagging, categorical and linear_tree configs.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.hist_pallas import MAX_FUSED_F
from ..ops.split import MAX_CAT_WORDS
from ..utils import LightGBMError
from ..utils.device import on_tpu


def split_fusion_default() -> bool:
    """Static packing-mode default: fused unless LGBM_TPU_SPLIT_FUSION
    is set to a falsy value (kill switch, read per trace — the learners
    pass it through a static jit arg so flipping the env retraces)."""
    return os.environ.get("LGBM_TPU_SPLIT_FUSION", "1") \
        not in ("0", "false", "off")


class SplitStepPlan(NamedTuple):
    """Which split step one learner's grow program runs. Resolved by
    ``plan_split_step`` and obeyed everywhere else: the learners pass
    it to their grow function as ONE static argument, set
    ``SplitParams.use_scan_kernel`` from it, and nothing downstream
    asks the platform or re-derives eligibility."""
    # "megakernel": the whole split is one pallas_call
    # (ops/split_step_pallas.py); "per_phase": partition_segment,
    # histogram_segment and the scans, one after the other
    body: str = "per_phase"
    # numeric scans by the Pallas scan kernel
    # (ops/split_scan_pallas.py), else by XLA
    scan_kernel: bool = False
    # the partition decision may come from the 256-entry table
    # (categorical bitset, EFB decode) instead of the threshold compare
    lut_partition: bool = False
    # the scan holds the categorical search (ops/split_categorical.py)
    cat_scan: bool = False
    # the table's width alone kept the megakernel away (more columns
    # than its unrolled body takes): what the trace-time counter
    # ``learner.wide_table_traces`` counts
    wide: bool = False


def plan_split_step(*, mode: str, params, bundled: bool,
                    num_bins_max: int, num_leaves: int,
                    num_features: int, forced_plan=(),
                    extra_trees: bool = False,
                    ff_bynode: float = 1.0, cache_hists: bool = True,
                    mv_groups: int = 0, serial_comm: bool = True,
                    interpret: bool = False,
                    has_megakernel: bool = False,
                    merged: bool | None = None,
                    tpu: bool | None = None) -> SplitStepPlan:
    """THE decision of which split step runs, from what can be
    observed: the platform (``tpu``; None asks ``on_tpu()``, the one
    place this choice asks it), ``mode`` = ``Config.fused_split_kernel``
    (auto / on / off), the table (``params.has_categorical``,
    ``bundled``, ``num_bins_max``, ``num_features`` = its physical
    columns), the training options that put
    per-split work between the phases, and the learner (``interpret``,
    ``has_megakernel``: only the single-device partitioned learner has
    one; ``serial_comm``: the mesh learners put collectives between the
    phases). docs/ARCHITECTURE.md holds the same rule as a table.

    The megakernel owns the whole split (leaf pick, partition,
    smaller-child histogram + sibling subtraction, both children's
    scans, state / tree / histogram writes), so whatever injects work
    it does not model keeps the per-phase body: CEGB's candidate cache,
    per-node randomness (extra-trees, by-node sampling), a pool-bounded
    histogram cache (no parent to subtract from), multi-val
    pseudo-groups, the legacy unpacked carry. ``auto`` takes it on a
    TPU where its compiled body applies (numeric, unbundled, byte bins,
    at most ``MAX_FUSED_F`` columns, no forced splits); ``on`` takes it wherever it is eligible, as the
    interpret twin off a TPU, and raises on a learner that has none. A
    kernel this selects and Mosaic refuses is a compile error, never a
    quiet run on the other body."""
    if tpu is None:
        tpu = on_tpu()
    if merged is None:
        merged = split_fusion_default()
    has_cat = bool(params.has_categorical)
    if mode == "on" and not has_megakernel:
        raise LightGBMError(
            "fused_split_kernel=on: this learner has no split-step "
            "megakernel, only tree_learner=partitioned on one device "
            "has (the serial learner's leaf_id layout has no compiled "
            "body: the TPU compiler refuses its row slabs, \"Slice "
            "shape along dimension 1 must be aligned to tiling (128), "
            "but is 28\"; the mesh learners' collectives sit between "
            "the phases)")
    # what the megakernel does not model keeps the per-phase body
    eligible = (has_megakernel and merged and cache_hists
                and serial_comm and not params.cegb_on
                and not extra_trees and ff_bynode >= 1.0
                and mv_groups == 0 and num_leaves >= 2)
    wide = False
    if mode == "on":
        # forced pre-steps run the per-phase body, and only the
        # interpret twin shares its histogram cache layout
        megakernel = eligible and (interpret or not forced_plan)
    elif mode == "auto":
        # the compiled body's static scope; its per-feature loops are
        # unrolled, so a wide table keeps the per-phase kernels, which
        # cut their work by columns past the same bound
        # (ops/hist_pallas.py MAX_FUSED_F and SLICE_F,
        # ops/split_scan_pallas.py SCAN_BLOCK_F)
        in_scope = (eligible and tpu and not forced_plan
                    and not has_cat and not bundled
                    and num_bins_max <= 256)
        wide = in_scope and num_features > MAX_FUSED_F
        megakernel = in_scope and not wide
    else:
        megakernel = False
    return SplitStepPlan(
        body="megakernel" if megakernel else "per_phase",
        # the scan kernel is numeric-only and compiled-only: the CPU
        # keeps the XLA scan so learners stay bit-equal there
        scan_kernel=bool(tpu and not interpret and not has_cat
                         and not params.cegb_on),
        lut_partition=has_cat or bool(bundled),
        cat_scan=has_cat, wide=wide)


def _bitcast_f32(x):
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _bitcast_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


class StatePack:
    """Packed grow-loop state.

    Legacy mode: [K, L] matrices (column = leaf) for the float/int
    per-leaf state and [K, L-1] matrices for the tree arrays — each
    split issues two column writes per state matrix plus one column
    write and two pointer fixups per tree matrix (the r05 layout).

    Fused mode: ONE i32 state matrix (float rows bitcast) and ONE i32
    tree matrix; each split issues one scatter per matrix. The carrier
    is the INTEGER type on purpose: integer moves keep every bit
    pattern, while an f32 carrier loses int rows on the TPU, where
    f32 data paths flush denormals (small ints) to zero and
    canonicalize NaN patterns (negative ints) — the first chip run
    trained correctly and returned trees whose split_feature /
    threshold_bin were all 0 and whose leaf pointers were 0x7FC00000. Fields listed in ``derived`` are
    not carried at all — ``view()`` synthesizes them — and ``pack()``
    drops them on repack. Bool fields ride the int rows; unlisted keys
    pass through the carry unchanged."""

    def __init__(self, sf, si, tf, ti,
                 bools=("bs_dleft", "bs_iscat"), merged=False,
                 derived=None):
        self.sf_fields, self.si_fields = tuple(sf), tuple(si)
        self.tf_fields, self.ti_fields = tuple(tf), tuple(ti)
        self.sf_idx = {k: i for i, k in enumerate(self.sf_fields)}
        self.si_idx = {k: i for i, k in enumerate(self.si_fields)}
        self.tf_idx = {k: i for i, k in enumerate(self.tf_fields)}
        self.ti_idx = {k: i for i, k in enumerate(self.ti_fields)}
        self.bools = frozenset(bools)
        self.merged = merged
        self.derived = dict(derived or {})
        self._packed = set(sf) | set(si) | set(tf) | set(ti)

    # field layouts shared by the serial (leaf_id) and partitioned
    # (segment) grow loops; the partitioned loop prepends its physical
    # segment bounds to the int fields
    GROW_SF = ("leaf_g", "leaf_h", "leaf_c", "bs_gain", "bs_lg",
               "bs_lh", "bs_lc", "bs_lout", "bs_rout", "leaf_cmin",
               "leaf_cmax", "leaf_value", "leaf_weight", "leaf_count")
    GROW_SI = ("bs_feat", "bs_thr", "bs_dleft", "bs_iscat", "ref_node",
               "ref_side", "leaf_parent", "leaf_depth")
    GROW_TF = ("split_gain_arr", "internal_value", "internal_weight",
               "internal_count")
    # left_child/right_child MUST stay adjacent: the fused pointer
    # fixup rewrites them as one contiguous 2-row dynamic slice
    GROW_TI = ("split_feature", "threshold_bin", "decision_type",
               "left_child", "right_child")

    # ---- pack / view -------------------------------------------------

    def pack(self, fields: dict) -> dict:
        """Plain per-field dict -> packed carry (one-time outside the
        while_loop; a mutated view repacks the same way — the stacks
        rebuild the matrices wholesale as a few concatenates). Derived
        fields are dropped from the carry."""
        st = {k: v for k, v in fields.items()
              if k not in self._packed and k not in self.derived}
        sfm = jnp.stack([fields[k].astype(jnp.float32)
                         for k in self.sf_fields])
        sim = jnp.stack([fields[k].astype(jnp.int32)
                         for k in self.si_fields])
        tfm = jnp.stack([fields[k].astype(jnp.float32)
                         for k in self.tf_fields])
        tim = jnp.stack([fields[k].astype(jnp.int32)
                         for k in self.ti_fields])
        if self.merged:
            st["S"] = jnp.concatenate([_bitcast_i32(sfm), sim], axis=0)
            st["T"] = jnp.concatenate([_bitcast_i32(tfm), tim], axis=0)
        else:
            st.update(SF=sfm, SI=sim, TF=tfm, TI=tim)
        return st

    _MATS = ("S", "T", "SF", "SI", "TF", "TI")

    def view(self, st: dict) -> dict:
        """Packed carry -> per-field dict of row VIEWS (static-index
        slices XLA folds away) plus the synthesized derived fields;
        shared helpers (forced_split_override, cegb_*) consume this
        unchanged."""
        v = {k: val for k, val in st.items() if k not in self._MATS}
        if self.merged:
            nf, nt = len(self.sf_fields), len(self.tf_fields)
            sfm, sim = _bitcast_f32(st["S"][:nf]), st["S"][nf:]
            tfm, tim = _bitcast_f32(st["T"][:nt]), st["T"][nt:]
        else:
            sfm, sim = st["SF"], st["SI"]
            tfm, tim = st["TF"], st["TI"]
        for k, i in self.sf_idx.items():
            v[k] = sfm[i]
        for k, i in self.si_idx.items():
            v[k] = sim[i].astype(bool) if k in self.bools else sim[i]
        for k, i in self.tf_idx.items():
            v[k] = tfm[i]
        for k, i in self.ti_idx.items():
            v[k] = tim[i]
        for k, fn in self.derived.items():
            v[k] = fn(v)
        return v

    # ---- per-split body helpers --------------------------------------

    def row_f(self, st: dict, name: str) -> jnp.ndarray:
        """One float state row [L] without materializing a full view
        (the while-loop cond needs only ``bs_gain``)."""
        if self.merged:
            return _bitcast_f32(st["S"][self.sf_idx[name]])
        return st["SF"][self.sf_idx[name]]

    def stack_f(self, vals: dict) -> jnp.ndarray:
        """[Ksf] f32 column from a name->scalar dict (extra names are
        ignored, so bodies may pass derived fields unconditionally)."""
        return jnp.stack([jnp.asarray(vals[k], jnp.float32)
                          for k in self.sf_fields])

    def stack_i(self, vals: dict) -> jnp.ndarray:
        return jnp.stack([jnp.asarray(vals[k], jnp.int32)
                          for k in self.si_fields])

    def read_site(self, st: dict, leaf) -> dict:
        """All per-leaf state of one leaf as name->scalar: ONE column
        slice in fused mode (two in legacy) instead of ~24 per-field
        scalar reads."""
        if self.merged:
            nf = len(self.sf_fields)
            col = st["S"][:, leaf]
            colf, coli = _bitcast_f32(col[:nf]), col[nf:]
        else:
            colf, coli = st["SF"][:, leaf], st["SI"][:, leaf]
        site = {k: colf[i] for k, i in self.sf_idx.items()}
        for k, i in self.si_idx.items():
            site[k] = coli[i].astype(bool) if k in self.bools \
                else coli[i]
        return site

    def set_state_cols(self, st: dict, idx_a, idx_b,
                       fa: dict, fb: dict, ia: dict, ib: dict) -> dict:
        """Write both fresh children's state columns (order-agnostic:
        the callers pass (small, other) or (leaf, new) index pairs).
        Fused mode: ONE two-column scatter; legacy: two column writes
        per state matrix. Returns the updated carry keys."""
        if self.merged:
            # ONE flat scalar stack reshaped to [K, 2] (row-major
            # interleave) — a single concatenate instead of per-matrix
            # column builds; the scalar bitcasts fuse into it
            flat = []
            for k in self.sf_fields:
                flat += [_bitcast_i32(jnp.asarray(fa[k], jnp.float32)),
                         _bitcast_i32(jnp.asarray(fb[k], jnp.float32))]
            for k in self.si_fields:
                flat += [jnp.asarray(ia[k], jnp.int32),
                         jnp.asarray(ib[k], jnp.int32)]
            cols = jnp.stack(flat).reshape(len(flat) // 2, 2)
            idx2 = jnp.stack([jnp.asarray(idx_a, jnp.int32),
                              jnp.asarray(idx_b, jnp.int32)])
            return {"S": st["S"].at[:, idx2].set(cols)}
        colfa, colfb = self.stack_f(fa), self.stack_f(fb)
        colia, colib = self.stack_i(ia), self.stack_i(ib)
        return {"SF": st["SF"].at[:, idx_a].set(colfa)
                .at[:, idx_b].set(colfb),
                "SI": st["SI"].at[:, idx_a].set(colia)
                .at[:, idx_b].set(colib)}

    def set_tree_col(self, st: dict, s, tf: dict, ti: dict,
                     pnode, upd, pside) -> dict:
        """Write internal node ``s``'s tree-array column and fix the
        parent node's child pointer (``pnode`` row ``left_child`` or
        ``right_child`` <- ``s`` when ``upd``). Fused mode: one column
        write + one contiguous 2-row read-modify-write; legacy: the
        r05 per-matrix writes."""
        colf = jnp.stack([jnp.asarray(tf[k], jnp.float32)
                          for k in self.tf_fields])
        coli = jnp.stack([jnp.asarray(ti[k], jnp.int32)
                          for k in self.ti_fields])
        if self.merged:
            # 0=left 1=right, aligned with the (left_child, right_child)
            # row pair
            side2 = jnp.arange(2, dtype=jnp.int32)[:, None]
            tm = st["T"].at[:, s].set(
                jnp.concatenate([_bitcast_i32(colf), coli]))
            r0 = len(self.tf_fields) + self.ti_idx["left_child"]
            pn = jnp.asarray(pnode, jnp.int32)
            old = jax.lax.dynamic_slice(tm, (r0, pn), (2, 1))
            new = jnp.where(upd & (pside == side2), s, old)
            tm = jax.lax.dynamic_update_slice(tm, new, (r0, pn))
            return {"T": tm}
        tfm = st["TF"].at[:, s].set(colf)
        tim = st["TI"].at[:, s].set(coli)
        lc_row = self.ti_idx["left_child"]
        rc_row = self.ti_idx["right_child"]
        tim = tim.at[lc_row, pnode].set(
            jnp.where(upd & (pside == 0), s, tim[lc_row, pnode]))
        tim = tim.at[rc_row, pnode].set(
            jnp.where(upd & (pside == 1), s, tim[rc_row, pnode]))
        return {"TF": tfm, "TI": tim}


@functools.lru_cache(maxsize=None)
def make_grow_pack(si_prefix=(), *, merged: bool, has_cat: bool,
                   has_monotone: bool, big_l: int) -> StatePack:
    """Grow-loop StatePack for one static config. Fused mode drops the
    derivable rows (leaf_weight/leaf_count/leaf_parent), the monotone
    bounds when no feature carries a monotone constraint, and the
    categorical bitsets on numerical-only datasets; ``view()``
    synthesizes them all so the shared helpers and the TreeArrays
    extraction are layout-blind. Cached: the megakernel takes the pack
    as a static jit argument (hashed by identity), so one static config
    must give one object."""
    sf = list(StatePack.GROW_SF)
    si = list(si_prefix) + list(StatePack.GROW_SI)
    derived = {}
    if merged:
        for name, src in (("leaf_weight", "leaf_h"),
                          ("leaf_count", "leaf_c"),
                          ("leaf_parent", "ref_node")):
            (sf if name in sf else si).remove(name)
            derived[name] = (lambda src_: lambda v: v[src_])(src)
        if not has_monotone:
            sf.remove("leaf_cmin")
            sf.remove("leaf_cmax")
            derived["leaf_cmin"] = \
                lambda v: jnp.full((big_l,), -jnp.inf, jnp.float32)
            derived["leaf_cmax"] = \
                lambda v: jnp.full((big_l,), jnp.inf, jnp.float32)
        if not has_cat:
            derived["bs_bitset"] = \
                lambda v: jnp.zeros((big_l, MAX_CAT_WORDS), jnp.uint32)
            derived["cat_bitsets"] = \
                lambda v: jnp.zeros((big_l - 1, MAX_CAT_WORDS),
                                    jnp.uint32)
    return StatePack(sf, si, StatePack.GROW_TF, StatePack.GROW_TI,
                     merged=merged, derived=derived)
