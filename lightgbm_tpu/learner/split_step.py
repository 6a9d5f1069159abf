"""Shared per-split step machinery for the fused grow loops.

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

import os

import jax
import jax.numpy as jnp

from ..ops.split import MAX_CAT_WORDS


def split_fusion_default() -> bool:
    """Static packing-mode default: fused unless LGBM_TPU_SPLIT_FUSION
    is set to a falsy value (kill switch, read per trace — the learners
    pass it through a static jit arg so flipping the env retraces)."""
    return os.environ.get("LGBM_TPU_SPLIT_FUSION", "1") \
        not in ("0", "false", "off")


def fused_split_kernel_mode(config_value: str = "auto") -> str:
    """Resolve the fused split-step megakernel gate
    (ops/split_step_pallas.py) to one of "on" / "off" / "auto".

    The LGBM_TPU_FUSED_SPLIT_KERNEL env var overrides the config param
    (same kill-switch ergonomics as LGBM_TPU_SPLIT_FUSION): 0/false/off
    force the per-phase foil, 1/on force the kernel (interpret twin on
    CPU — the census/test vehicle), anything else keeps "auto" =
    default on where lowerable (compiled backends whose Mosaic accepts
    the kernel; the probe emits a reason_code when it cannot lower)."""
    env = os.environ.get("LGBM_TPU_FUSED_SPLIT_KERNEL", "").lower()
    if env in ("0", "false", "off"):
        return "off"
    if env in ("1", "on", "force"):
        return "on"
    if env in ("auto",):
        return "auto"
    return config_value if config_value in ("on", "off") else "auto"


def fused_split_eligible(params, *, cache_hists: bool, merged: bool,
                         extra_trees: bool, ff_bynode: float,
                         mv_groups: int = 0, serial_comm: bool = True,
                         num_leaves: int = 0) -> bool:
    """STATIC eligibility of the fused split-step megakernel for one
    grow trace. The kernel owns the whole split — leaf pick, partition,
    smaller-child histogram + sibling subtraction, both children's
    scans, state/tree/hist writes — so anything that injects per-split
    work the kernel does not model falls back to the per-phase foil:
    CEGB (candidate-cache bookkeeping), per-node RNG (extra-trees /
    by-node sampling), pool-bounded histogram memory (no parent to
    subtract from), multi-val pseudo-groups, and non-serial comms
    (collectives must sit between phases, never inside one kernel)."""
    return (merged and cache_hists and serial_comm
            and not params.cegb_on and not extra_trees
            and ff_bynode >= 1.0 and mv_groups == 0
            and num_leaves >= 2)


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


def make_grow_pack(si_prefix=(), *, merged: bool, has_cat: bool,
                   has_monotone: bool, big_l: int) -> StatePack:
    """Grow-loop StatePack for one static config. Fused mode drops the
    derivable rows (leaf_weight/leaf_count/leaf_parent), the monotone
    bounds when no feature carries a monotone constraint, and the
    categorical bitsets on numerical-only datasets; ``view()``
    synthesizes them all so the shared helpers and the TreeArrays
    extraction are layout-blind."""
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


def set_bitsets(pack: StatePack, view: dict, idx_a, idx_b,
                bits_a, bits_b, s, site_bitset) -> dict:
    """Bitset carry updates for one split — compiled out entirely when
    the pack derives the bitsets (numerical-only datasets)."""
    if "bs_bitset" in pack.derived:
        return {}
    idx2 = jnp.stack([jnp.asarray(idx_a, jnp.int32),
                      jnp.asarray(idx_b, jnp.int32)])
    return {
        "bs_bitset": view["bs_bitset"].at[idx2].set(
            jnp.stack([bits_a, bits_b])),
        "cat_bitsets": view["cat_bitsets"].at[s].set(site_bitset)}


def child_constraints(meta, feat, is_cat, lout, rout, pcmin, pcmax,
                      has_monotone: bool):
    """Monotone constraint propagation to both children
    (LeafConstraints::UpdateConstraints, monotone_constraints.hpp:44).
    STATICALLY compiled out (inherited parent bounds, which stay ±inf
    forever) when no feature has a monotone constraint."""
    if not has_monotone:
        return pcmin, pcmax, pcmin, pcmax
    return child_constraints_mono(meta.monotone[feat], is_cat, lout,
                                  rout, pcmin, pcmax)


def child_constraints_mono(mono, is_cat, lout, rout, pcmin, pcmax):
    """``child_constraints`` on a pre-gathered per-feature monotone
    direction — the fused megakernel's Mosaic body extracts ``mono``
    with a select-sum (dynamic gathers do not lower) and shares the
    rest of the math here."""
    mid = (lout + rout) * 0.5
    numerical = ~is_cat
    cmin_l = jnp.where(numerical & (mono < 0),
                       jnp.maximum(pcmin, mid), pcmin)
    cmax_l = jnp.where(numerical & (mono > 0),
                       jnp.minimum(pcmax, mid), pcmax)
    cmin_r = jnp.where(numerical & (mono > 0),
                       jnp.maximum(pcmin, mid), pcmin)
    cmax_r = jnp.where(numerical & (mono < 0),
                       jnp.minimum(pcmax, mid), pcmax)
    return cmin_l, cmax_l, cmin_r, cmax_r


def order_child_pair(a_is_left, k, lg, lh, lc, rg, rh, rc, lout, rout,
                     cmin_l, cmax_l, cmin_r, cmax_r) -> dict:
    """(left, right) child scalars -> (a, b) storage order for one
    split step. ``a_is_left`` is True on the (leaf, new) paths and
    ``small_is_left`` on the (smaller, other) fused path; the salts
    carry the child identity (left = 2k+1, right = 2k+2) so per-node
    RNG streams are order-invariant, and ``side_a/b`` keep the
    ref_side encoding (0 = left child). One definition shared by the
    serial and partitioned grow bodies — this mapping is
    bit-exactness-critical and must never diverge between them."""
    def w(x, y):
        return jnp.where(a_is_left, x, y)

    side_a = w(jnp.int32(0), jnp.int32(1))
    return dict(
        ga=w(lg, rg), ha=w(lh, rh), ca=w(lc, rc),
        gb=w(rg, lg), hb=w(rh, lh), cb=w(rc, lc),
        out_a=w(lout, rout), out_b=w(rout, lout),
        cmin_a=w(cmin_l, cmin_r), cmax_a=w(cmax_l, cmax_r),
        cmin_b=w(cmin_r, cmin_l), cmax_b=w(cmax_r, cmax_l),
        salt_a=w(2 * k + 1, 2 * k + 2),
        salt_b=w(2 * k + 2, 2 * k + 1),
        side_a=side_a, side_b=jnp.int32(1) - side_a)


def child_columns(split, g, h, c, out, cmin, cmax, s, side, depth,
                  extra_i=None):
    """One fresh child's state-column field dicts (float, int) for
    ``StatePack.set_state_cols`` — the single definition of what each
    split writes per child (the partitioned learner prepends its
    segment bounds via ``extra_i``)."""
    f = dict(leaf_g=g, leaf_h=h, leaf_c=c, bs_gain=split.gain,
             bs_lg=split.left_g, bs_lh=split.left_h,
             bs_lc=split.left_c, bs_lout=split.left_output,
             bs_rout=split.right_output, leaf_cmin=cmin,
             leaf_cmax=cmax, leaf_value=out, leaf_weight=h,
             leaf_count=c)
    i = dict(bs_feat=split.feature, bs_thr=split.threshold,
             bs_dleft=split.default_left, bs_iscat=split.is_cat,
             ref_node=s, ref_side=side, leaf_parent=s,
             leaf_depth=depth)
    if extra_i:
        i.update(extra_i)
    return f, i


def make_scan_leaf(comm, meta_scan, params, feature_mask, node_rand,
                   bundled: bool, max_depth: int, select=None):
    """One leaf's best-split scan (debundle -> per-node randomness ->
    comm.select_split -> max_depth blocking) — ONE definition shared by
    the serial and partitioned grow bodies AND the fused megakernel's
    interpret twin (ops/split_step_pallas.py). The twin's byte-exact
    parity with the foil rests on this being the same function.
    ``select`` overrides ``comm.select_split`` where the root and
    per-split scan layouts differ (the data-parallel reduce-scatter
    recipe scans the root replicated, learner/comm.py)."""
    if select is None:
        select = comm.select_split

    def scan_leaf(hist, g, h, c, depth, cmin, cmax, salt):
        if bundled:
            from ..ops.histogram import debundle_leaf_hist
            hist = debundle_leaf_hist(hist, meta_scan, g, h, c,
                                      comm.local_hist)
        rb, nm = node_rand(salt)
        fm = feature_mask if nm is None else nm  # nm already in-subset
        res = select(hist, g, h, c, meta_scan, params,
                     cmin, cmax, fm, rand_bins=rb)
        blocked = (max_depth > 0) & (depth >= max_depth)
        return res._replace(gain=jnp.where(blocked, -jnp.inf, res.gain))
    return scan_leaf


def scan_split_pair(comm, scan_leaf, a_is_left, k, depth,
                    hist_a, hist_b, lg, lh, lc, rg, rh, rc, lout, rout,
                    cmin_l, cmax_l, cmin_r, cmax_r):
    """Order the (a, b) child pair and scan both fresh children — the
    shared non-CEGB composition of ``order_child_pair`` +
    ``scan_children`` used by both grow bodies and the megakernel
    twin."""
    o = order_child_pair(a_is_left, k, lg, lh, lc, rg, rh, rc, lout,
                         rout, cmin_l, cmax_l, cmin_r, cmax_r)
    split_a, split_b = scan_children(
        comm, scan_leaf, hist_a, hist_b, o["ga"], o["ha"], o["ca"],
        o["gb"], o["hb"], o["cb"], depth, o["cmin_a"], o["cmax_a"],
        o["cmin_b"], o["cmax_b"], o["salt_a"], o["salt_b"])
    return o, split_a, split_b


def split_node_updates(params, gain, feat, thr, dleft, is_cat,
                       pg, ph, pc, ref_node, leaf, new):
    """Tree-array column dicts + parent-pointer fixup scalars of one
    split — one definition shared by the grow bodies and the fused
    megakernel twin (``set_tree_col`` consumes the result)."""
    from ..ops.split import leaf_output_no_constraint
    dec = jnp.where(is_cat, 1, 0) + jnp.where(dleft, 2, 0)
    upd = ref_node >= 0
    pnode = jnp.where(upd, ref_node, 0)
    parent_out = leaf_output_no_constraint(
        pg, ph + 2e-15, params.lambda_l1, params.lambda_l2,
        params.max_delta_step)
    treef = dict(split_gain_arr=gain, internal_value=parent_out,
                 internal_weight=ph, internal_count=pc)
    treei = dict(split_feature=feat, threshold_bin=thr,
                 decision_type=dec, left_child=~leaf, right_child=~new)
    return treef, treei, pnode, upd


def scan_children(comm, scan_leaf, hist_a, hist_b, ga, ha, ca,
                  gb, hb, cb, depth, cmin_a, cmax_a, cmin_b, cmax_b,
                  salt_a, salt_b):
    """Best splits of both fresh children (order-agnostic pair — the
    fused bodies pass (smaller, larger), the legacy CEGB path passes
    (left, right); the salts carry the child identity so node-rand
    streams stay exact). For vmap_safe comms this is ONE vmapped scan:
    same math, half the op count inside the while_loop body (each
    [F, B] scan op is tiny; per-op overhead dominates at bench
    shapes). Collective-bearing selects stay unbatched. Shared by the
    serial and partitioned grow loops."""
    if not comm.vmap_safe:
        return (scan_leaf(hist_a, ga, ha, ca, depth, cmin_a, cmax_a,
                          salt_a),
                scan_leaf(hist_b, gb, hb, cb, depth, cmin_b, cmax_b,
                          salt_b))
    res2 = jax.vmap(
        lambda hh, g_, h_, c_, cm, cx, s_: scan_leaf(
            hh, g_, h_, c_, depth, cm, cx, s_))(
        jnp.stack([hist_a, hist_b]),
        jnp.stack([ga, gb]), jnp.stack([ha, hb]),
        jnp.stack([ca, cb]),
        jnp.stack([cmin_a, cmin_b]),
        jnp.stack([cmax_a, cmax_b]),
        jnp.stack([salt_a, salt_b]))
    return (jax.tree.map(lambda x: x[0], res2),
            jax.tree.map(lambda x: x[1], res2))
