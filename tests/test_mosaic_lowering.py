"""Cross-platform Mosaic lowering of every production Pallas kernel.

Interpret mode provably catches NONE of Mosaic's hardware-compile
failures — in round 4 both kernels failed their first real-v5e compile
(unsupported u8<->f32 casts, VMEM layout issues) after a fully green
CPU suite. ``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))``
runs the REAL Mosaic lowering pass on any host, no TPU needed, and
rejects unsupported casts, illegal block specs, and bad scratch shapes
at trace time. (The backend compiler's VMEM allocation is still
hardware-only — tools/check_kernels_on_chip.py covers that half.)

Every kernel is lowered in the exact call shape the production path
uses (incl. the vmapped split-scan, which batches its SMEM operands —
a historically miscompiling shape).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.hist_pallas import build_matrix, pack_gh
from lightgbm_tpu.utils import LightGBMError


def _mat(n=4096, f=28, b=256, seed=0):
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, b, (n, f))
    mat = build_matrix(jnp.asarray(binned), 2048)
    return pack_gh(mat, f,
                   jnp.asarray(rng.randn(n).astype(np.float32)),
                   jnp.asarray(rng.rand(n).astype(np.float32) + 0.1),
                   jnp.asarray(np.ones(n, np.float32)))


def _lowers(fn, *args):
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("f", [28, 69, 192])
def test_histogram_kernel_lowers_for_tpu(f):
    """The whole-row one-hot stream: the Higgs width, the narrowest
    table that used to be sliced, the widest that is not."""
    from lightgbm_tpu.ops.hist_pallas import histogram_segment
    b = 256
    mat = _mat(f=f, b=b)
    _lowers(functools.partial(histogram_segment, num_bins=b,
                              num_features=f, interpret=False),
            mat, jnp.int32(8), jnp.int32(2048))


@pytest.mark.parametrize("use_lut", [True, False])
def test_partition_v1_lowers_for_tpu(use_lut):
    from lightgbm_tpu.ops.partition_pallas import partition_segment
    mat = _mat()
    lut = jnp.zeros((1, 256), jnp.float32)
    _lowers(functools.partial(partition_segment, blk=512,
                              interpret=False, use_lut_path=use_lut),
            mat, jnp.zeros_like(mat), jnp.int32(13), jnp.int32(2000),
            14, jnp.int32(128), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(256), jnp.int32(0), lut)


def test_lut_partition_lowers_at_the_categorical_width():
    """The bitset (LUT) partition at the benchmark's categorical table:
    40 columns in the 128-byte row, a segment that starts off the
    8-row granule."""
    from lightgbm_tpu.ops.partition_pallas import (bitset_to_lut,
                                                   partition_segment)
    mat = _mat(f=40, b=255)
    lut = bitset_to_lut(jnp.asarray([0x5, 0, 0, 0, 0, 0, 0, 1 << 30],
                                    jnp.uint32))
    _lowers(functools.partial(partition_segment, blk=512,
                              interpret=False, use_lut_path=True),
            mat, jnp.zeros_like(mat), jnp.int32(13), jnp.int32(3000),
            jnp.int32(5), jnp.int32(0), jnp.int32(0), jnp.int32(2),
            jnp.int32(0), jnp.int32(255), jnp.int32(1), lut)


def test_fused_split_step_lowers_for_tpu():
    """The split-step megakernel's Mosaic body lowers on this host.
    The ``auto`` rule is static (no lowering probe behind it), so a
    kernel Mosaic refuses would fail every eligible TPU run at its
    first grow call — CI fails FIRST. Notably the body's partition
    phase keeps all its lane/row extractions as f32 select-sums.
    Lowering is not compiling: only ``chip_smoke.py`` shows the chip's
    compiler accepts the body."""
    from lightgbm_tpu.learner.partitioned import segment_grow_pack
    from lightgbm_tpu.ops.split_step_pallas import lower_for_tpu
    lower_for_tpu(segment_grow_pack(15), big_l=15)


def test_leaf_layout_on_a_tpu_raises():
    """``fused_split_kernel=on`` with the serial learner is an error
    naming the compiler's refusal of its layout, on every platform:
    not a run on another path."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.serial import SerialTreeLearner

    rng = np.random.RandomState(0)
    X = rng.randn(512, 6).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "fused_split_kernel": "on",
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=(X[:, 0] > 0).astype(float))
    with pytest.raises(LightGBMError, match="aligned to tiling"):
        SerialTreeLearner(ds, cfg, hist_method="onehot")


def test_refused_kernel_raises_not_falls_back(monkeypatch):
    """A kernel the gate selects and Mosaic refuses is an error with
    the compiler's message — never a quiet run on the per-phase
    kernels. Force the known refusal (an f32 ``tpu.iota``) into the
    megakernel body, make the platform read as a TPU (in the ONE
    module that asks it for this choice) so ``auto`` selects the
    kernel, and lower the training block."""
    import lightgbm_tpu.learner.split_step as split_step
    import lightgbm_tpu.ops.split_step_pallas as sp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner

    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    monkeypatch.setattr(
        sp, "_iota_f32",
        lambda shape, dim: jax.lax.broadcasted_iota(
            jnp.float32, shape, dim))
    rng = np.random.RandomState(0)
    X = rng.randn(512, 6).astype(np.float32)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=(X[:, 0] > 0).astype(float))
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    assert ln.params.use_scan_kernel
    assert ln.split_plan().body == "megakernel"
    g = jnp.zeros((512,), jnp.float32)
    with pytest.raises(Exception, match="tpu.iota"):
        jax.jit(functools.partial(ln.traceable_grow,
                                  meta=ln.grow_operands())).trace(
            ln.mat, ln.ws, g, g + 1.0).lower(
            lowering_platforms=("tpu",))


def _scan_args(f=28, b=256, seed=1):
    rng = np.random.RandomState(seed)
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    meta = FeatureMeta(
        num_bins=jnp.asarray(rng.randint(3, b, f), jnp.int32),
        missing=jnp.asarray(rng.randint(0, 3, f), jnp.int32),
        default_bin=jnp.asarray(rng.randint(0, 5, f), jnp.int32),
        most_freq_bin=jnp.zeros(f, jnp.int32),
        monotone=jnp.zeros(f, jnp.int32),
        penalty=jnp.ones(f, jnp.float32),
        is_categorical=jnp.zeros(f, bool),
        global_id=jnp.arange(f, dtype=jnp.int32))
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=0.5, max_delta_step=0.0,
        min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=True,
        use_scan_kernel=True)
    hist = jnp.asarray(rng.rand(f, b, 3).astype(np.float32))
    inf = jnp.float32(np.inf)
    dyn = (hist, jnp.float32(100.0), jnp.float32(200.0),
           jnp.float32(4096.0), -inf, inf, jnp.ones(f, bool))
    return dyn, meta, params


def test_split_scan_kernel_lowers_for_tpu():
    from lightgbm_tpu.ops.split_scan_pallas import \
        per_feature_numerical_pallas
    (hist, pg, ph, pc, lo, hi, fm), meta, params = _scan_args()
    # meta/params ride as closed-over constants like the grow loop's
    # trace (params holds static python floats, never tracers).
    # interpret=False is REQUIRED: the wrapper's backend-resolved
    # default is True on this CPU host, which lowered the interpret
    # emulation instead of Mosaic and silently passed while the real
    # kernel carried unlowerable i32 reductions (fixed alongside the
    # split-step megakernel: the threshold arg-extrema now run in
    # exact f32)
    _lowers(lambda hh: per_feature_numerical_pallas(
        hh, pg, ph, pc, meta, params, lo, hi, fm, interpret=False),
        hist)


def test_split_scan_vmapped_lowers_for_tpu():
    """The grow loop always calls the kernel under vmap over both
    children; 1-D SMEM operands batch to illegal block specs unless
    they carry a leading unit dim — lower the BATCHED shape."""
    from lightgbm_tpu.ops.split_scan_pallas import \
        per_feature_numerical_pallas
    (hist, pg, ph, pc, lo, hi, fm), meta, params = _scan_args()
    hist2 = jnp.stack([hist, hist * 0.5])

    def batched(hh2):
        return jax.vmap(lambda hh: per_feature_numerical_pallas(
            hh, pg, ph, pc, meta, params, lo, hi, fm,
            interpret=False))(hh2)
    _lowers(batched, hist2)


@pytest.mark.parametrize("leaves,f", [(15, 12), (255, 28)])
def test_full_fused_training_block_lowers_for_tpu(leaves, f):
    """The ENTIRE fused-iteration device program — gradients -> grow
    (compiled Pallas hist/partition/scan kernels) -> score update,
    scanned over m iterations — lowers for TPU on this host. This is
    the program bench.py dispatches; a Mosaic regression anywhere in
    the grow loop fails HERE instead of on the chip."""
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.models.gbdt import GBDT, _fused_iter_block

    rng = np.random.RandomState(0)
    X = rng.randn(512, f).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": leaves,
        "tree_learner": "partitioned", "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y)
    b = GBDT(cfg, ds)
    # compiled-kernel learner (interpret=False) like the real chip
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    assert ln.supports_fused_scan and ln.fused_scan_ok()

    fused = jax.jit(
        functools.partial(_fused_iter_block, learner=ln,
                          grad_fn=b._grad_fn, bag_fn=None,
                          valid_data=(), k=1),
        static_argnames=("m",))
    fused.trace(ln.mat, ln.ws, b.train_score, (), jnp.float32(0.1),
                jnp.int32(0), m=4).lower(lowering_platforms=("tpu",))


def test_categorical_fused_training_block_lowers_for_tpu():
    """The fused block of a table with categorical columns, at the
    benchmark's 40 columns and 255 leaves: the plan keeps it off the
    compiled megakernel (``plan_split_step``), so the grow loop holds the
    per-phase kernels (bitset partition, segment histogram) and the
    numeric and categorical XLA scans, and it still rides the fused
    driver. Lowered with the compiled kernels, as the chip runs it."""
    import lightgbm_tpu.learner.split_step as split_step
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.models.gbdt import GBDT, _fused_iter_block
    from lightgbm_tpu.observability.telemetry import get_telemetry

    rng = np.random.RandomState(0)
    cats = list(range(10))
    X = rng.randn(2048, 40).astype(np.float32)
    X[:, :10] = rng.randint(0, 200, (2048, 10)) % np.asarray(
        [22, 12, 31, 7, 29, 200, 200, 2, 4, 2])
    y = (X[:, 4] % 2 + X[:, 12] > 0.5).astype(np.float64)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 255, "verbosity": -1})
    ds = Dataset.from_numpy(X, cfg, label=y, categorical_features=cats)
    b = GBDT(cfg, ds)
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    assert ln.params.has_categorical and not ln.bundled
    assert not ln.params.use_scan_kernel
    # what the chip's plan is for this table: no megakernel even there
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(split_step, "on_tpu", lambda: True)
        assert ln.split_plan() == split_step.SplitStepPlan(
            "per_phase", False, True, True)
    assert ln.supports_fused_scan and ln.fused_scan_ok()
    tel = get_telemetry()
    was_on = tel.enabled
    tel.ensure_ring()
    before = {k: tel.counters.get(k, 0) for k in (
        "learner.lut_partition_traces", "learner.cat_scan_traces",
        "learner.megakernel_traces")}
    fused = jax.jit(
        functools.partial(_fused_iter_block, learner=ln,
                          grad_fn=b._grad_fn, bag_fn=None,
                          valid_data=(), k=1),
        static_argnames=("m",))
    text = fused.trace(ln.mat, ln.ws, b.train_score, (), jnp.float32(0.1),
                       jnp.int32(0), m=2).lower(
        lowering_platforms=("tpu",)).as_text()
    got = {k: tel.counters.get(k, 0) - v for k, v in before.items()}
    if not was_on:
        tel.reset()
    assert got == {"learner.lut_partition_traces": 1,
                   "learner.cat_scan_traces": 1,
                   "learner.megakernel_traces": 0}
    # the per-phase kernels are Mosaic calls in the lowered module
    assert text.count("tpu_custom_call") >= 3


def test_a_column_wider_than_a_byte_raises():
    """The device route packs a bin in a byte and says so when a column
    has more than 256 bins: it does not train on another learner. Only
    a numeric column can: a categorical one is cut to min(max_bin, 256)
    bins by the binning (``binning.MAX_CATEGORICAL_BINS``), so the same
    table with the wide column named a category is taken."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    rng = np.random.RandomState(0)
    X = rng.randint(0, 600, (8192, 3)).astype(np.float32)
    y = (X[:, 0] % 2).astype(float)
    cfg = Config.from_params({"objective": "binary", "max_bin": 400,
                              "verbosity": -1})
    ds = Dataset.from_numpy(X[:, :1], cfg, label=y)
    assert ds.num_bins_array()[0] > 256
    with pytest.raises(ValueError, match="max 256 bins per feature"):
        PartitionedTreeLearner(ds, cfg, interpret=False)
    ds = Dataset.from_numpy(X[:, :1], cfg, label=y,
                            categorical_features=[0])
    assert ds.num_bins_array()[0] == 256
    assert PartitionedTreeLearner(ds, cfg, interpret=False).num_bins_max \
        == 256


@pytest.mark.parametrize("f", [193, 250, 2000])
def test_histogram_wide_slices_lower_for_tpu(f):
    """The sliced one-hot stream (one ``pallas_call``, the column
    slice a grid axis, two-region DMA: the slice's columns and the
    payload's lane tile) lowers for TPU: the narrowest table past
    ``MAX_FUSED_F`` and 250 columns (two slices each), the Epsilon
    table's sixteen."""
    from lightgbm_tpu.ops.hist_pallas import histogram_segment
    b = 256
    mat = _mat(n=2048, f=f, b=b)
    _lowers(functools.partial(histogram_segment, num_bins=b,
                              num_features=f, interpret=False),
            mat, jnp.int32(8), jnp.int32(1024))


def test_partition_lowers_at_the_wide_row():
    """``partition_segment`` over 2,048-byte rows (2,000 columns)."""
    from lightgbm_tpu.ops.partition_pallas import partition_segment
    mat = _mat(n=2048, f=2000)
    assert mat.shape[1] == 2048
    _lowers(functools.partial(partition_segment, blk=512,
                              interpret=False, use_lut_path=False),
            mat, jnp.zeros_like(mat), jnp.int32(13), jnp.int32(1500),
            jnp.int32(1999), jnp.int32(128), jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(256), jnp.int32(0),
            jnp.zeros((1, 256), jnp.float32))


@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["one-leaf", "both-children"])
def test_feature_blocked_scan_lowers_for_tpu(vmapped):
    """The scan at 2,000 features: a grid over blocks of 128 features,
    alone and under the grow loop's vmap over both children."""
    from lightgbm_tpu.ops.split_scan_pallas import \
        per_feature_numerical_pallas
    (hist, pg, ph, pc, lo, hi, fm), meta, params = _scan_args(f=2000)

    def one(hh):
        return per_feature_numerical_pallas(
            hh, pg, ph, pc, meta, params, lo, hi, fm, interpret=False)
    if vmapped:
        _lowers(jax.vmap(one), jnp.stack([hist, hist * 0.5]))
    else:
        _lowers(one, hist)


@pytest.mark.parametrize("num_leaves", [255, 4096])
def test_leaf_of_pos_block_pass_lowers_for_tpu(num_leaves):
    """The grow program's last kernel, at the benchmark's row count and
    at the largest table the block pass takes (two SMEM tables)."""
    from lightgbm_tpu.ops.leaf_of_pos import (DENSE_MAX_LEAVES,
                                              leaf_of_pos)
    assert num_leaves <= DENSE_MAX_LEAVES
    table = jax.ShapeDtypeStruct((num_leaves,), jnp.int32)
    _lowers(functools.partial(leaf_of_pos, n=10_500_000,
                              interpret=False),
            table, table, jax.ShapeDtypeStruct((), jnp.int32))


# ---- PR 28: the pipelined partition stream ---------------------------

def test_both_partition_kernels_lower_the_pipelined_stream():
    """``partition_segment`` (LUT on and off) and the megakernel's
    phase 0 lower ONE block step, ``partition_pallas.partition_stream``:
    the trace-time counter says the helper entered each trace."""
    from lightgbm_tpu.learner.partitioned import segment_grow_pack
    from lightgbm_tpu.observability.telemetry import get_telemetry
    from lightgbm_tpu.ops import partition_pallas, split_step_pallas
    assert not hasattr(split_step_pallas, "compact_and_write")
    tel = get_telemetry()
    was_on = tel.enabled
    tel.ensure_ring()
    name = "kernels.partition_pipelined"
    one = "kernels.partition_one_compaction"
    child = "kernels.hist_child_stream"
    before = tel.counters.get(name, 0)
    one_before = tel.counters.get(one, 0)
    child_before = tel.counters.get(child, 0)
    mat = _mat(n=6000)          # a shape no other test traced
    lut = jnp.zeros((1, 256), jnp.float32)
    for use_lut in (True, False):
        _lowers(functools.partial(partition_pallas.partition_segment,
                                  blk=512, interpret=False,
                                  use_lut_path=use_lut),
                mat, jnp.zeros_like(mat), jnp.int32(13),
                jnp.int32(5000), 14, jnp.int32(128), jnp.int32(0),
                jnp.int32(0), jnp.int32(0), jnp.int32(256),
                jnp.int32(0), lut)
    after_partition = tel.counters.get(name, 0)
    child_after_partition = tel.counters.get(child, 0)
    jax.clear_caches()
    split_step_pallas.lower_for_tpu(segment_grow_pack(15), big_l=15)
    after_mega = tel.counters.get(name, 0)
    one_after_mega = tel.counters.get(one, 0)
    child_after_mega = tel.counters.get(child, 0)
    if not was_on:
        tel.reset()
    assert after_partition - before == 2
    assert after_mega - after_partition >= 1
    # PR 34: one compaction (counted where its one-hot and product
    # enter the trace) a stream traced, through both kernels' lowering;
    # the block step before it would count 3 a stream
    assert one_after_mega - one_before == after_mega - before
    # PR 30: only the megakernel's phase 0 holds the histogram stream
    # over the smaller child's segment, behind its partition stream
    assert child_after_partition == child_before
    assert child_after_mega - child_after_partition >= 1


@pytest.fixture(scope="module")
def one_chip():
    """A described TPU v5e to compile for (no chip attached): what the
    chip's compiler refuses, it refuses here. Made inside the fixture
    so only the worker that runs this file loads the TPU's library."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("use_lut", [True, False])
def test_pipelined_partition_compiles_for_v5e(one_chip, use_lut):
    """The real size: a 1 M-row, 128-byte-row matrix. Dynamic slot
    indices on the u8 buffers, ``pl.when`` around DMAs inside
    ``fori_loop`` and the carried heads' 8-row slices all pass the
    chip's compiler."""
    from lightgbm_tpu.ops.partition_pallas import partition_segment
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    mat = sds((1_003_528, 128), jnp.uint8)
    i32 = sds((), jnp.int32)
    jax.jit(functools.partial(
        partition_segment, blk=512, use_lut_path=use_lut)).lower(
        mat, mat, *([i32] * 9), sds((1, 256), jnp.float32)).compile()


@pytest.mark.parametrize("f,n", [(28, 10_500_000), (67, 7_000_000),
                                 (192, 1_000_000)],
                         ids=["higgs-10m", "criteo-7m", "192-columns"])
def test_pipelined_megakernel_compiles_for_v5e(one_chip, f, n):
    """The megakernel at the two megakernel cells' shapes (10.5 M x 28
    and 7 M x 67, 255 leaves, 256 bins): the shared partition stream
    and, behind it, the histogram stream over the smaller child's
    segment (PR 30) in phase 0. And at ``MAX_FUSED_F`` = 192 columns
    (PR 34), its widest row: the stream's scratch, two staged windows
    since the one-compaction block step, beside the largest ``hpl``."""
    from lightgbm_tpu.learner.partitioned import segment_grow_pack
    from lightgbm_tpu.ops import split_step_pallas as ssp
    from lightgbm_tpu.ops.hist_pallas import matrix_cols, matrix_rows
    from lightgbm_tpu.ops.split import SplitParams
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    big_l, b = 255, 256
    params = SplitParams(
        lambda_l1=0.0, lambda_l2=1.0, max_delta_step=0.0,
        min_data_in_leaf=20.0, min_sum_hessian_in_leaf=1e-3,
        min_gain_to_split=0.0, any_missing=False)
    pack = segment_grow_pack(big_l)
    S = sds((len(pack.sf_fields) + len(pack.si_fields), big_l),
            jnp.int32)
    T = sds((len(pack.tf_fields) + len(pack.ti_fields), big_l - 1),
            jnp.int32)
    hist = sds((big_l, 3, -(-f // 8) * 8, -(-b // 128) * 128),
               jnp.float32)
    mat = sds((matrix_rows(n, ssp.FUSED_BLK), matrix_cols(f)),
              jnp.uint8)
    jax.jit(functools.partial(
        ssp.fused_split_step_segment, params=params, pack=pack,
        big_l=big_l, max_depth=-1, b=b, f=f,
        n=n, bundled=False, has_monotone=False, blk=ssp.FUSED_BLK,
        interpret=False)).lower(
        sds((), jnp.int32), S, T, mat, mat, hist,
        sds((f, 8), jnp.int32), sds((f, 2), jnp.float32)).compile()


def test_partition_kernels_ask_for_no_more_scoped_vmem(one_chip,
                                                        monkeypatch):
    """The one-compaction block step (PR 34) holds two staged windows
    where the parent held one. Both kernels ask for the scoped-VMEM
    limit they asked for before, and at the 128-byte rows of four
    cells ``partition_segment`` needs no raised limit at all: with the
    request taken out of its compiler parameters it compiles for the
    described v5e under the default one. (At 2,048-byte rows the
    parent's kernel needed the raise too.)"""
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.ops import partition_pallas, split_step_pallas

    asked = []

    class Params:
        @staticmethod
        def CompilerParams(**kw):
            asked.append(kw.pop("vmem_limit_bytes", None))
            return pltpu.CompilerParams(**kw)

        def __getattr__(self, name):
            return getattr(pltpu, name)

    assert split_step_pallas.VMEM_LIMIT == 100 * 1024 * 1024
    monkeypatch.setattr(partition_pallas, "pltpu", Params())
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    mat = sds((1_003_520, 128), jnp.uint8)   # a shape no test traced
    i32 = sds((), jnp.int32)
    for use_lut in (True, False):
        jax.jit(functools.partial(
            partition_pallas.partition_segment, blk=512,
            use_lut_path=use_lut)).lower(
            mat, mat, *([i32] * 9), sds((1, 256), jnp.float32)).compile()
    assert asked == [100 * 1024 * 1024] * 2


@pytest.mark.parametrize("f,n", [
    pytest.param(28, 10_500_000, id="higgs-10m"),
    pytest.param(67, 7_000_000, id="criteo-7m"),
    pytest.param(40, 10_000_000, id="expo-10m"),
    pytest.param(69, 1_000_000, id="69-columns"),
    pytest.param(128, 1_000_000, id="128-columns"),
    pytest.param(192, 1_000_000, id="192-columns")])
def test_whole_row_histogram_compiles_for_v5e(one_chip, f, n):
    """``histogram_segment`` up to ``MAX_FUSED_F`` columns, the
    whole-row one-hot stream (the root's histogram and a leaf
    segment's), on the learner's matrix: the three narrow cells'
    shapes; 69 columns, where the deleted nibble kernel ran out of
    scoped VMEM; one whole lane tile of bins; and the bound itself, the
    longest unrolled body."""
    from lightgbm_tpu.learner.partitioned import HIST_BLK
    from lightgbm_tpu.ops.hist_pallas import (MAX_FUSED_F,
                                              histogram_segment,
                                              matrix_cols, matrix_rows)
    assert f <= MAX_FUSED_F
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = sds((), jnp.int32)
    jax.jit(functools.partial(
        histogram_segment, num_bins=256, num_features=f,
        blk=HIST_BLK)).lower(
        sds((matrix_rows(n, HIST_BLK), matrix_cols(f)), jnp.uint8),
        i32, i32).compile()


# ---- PR 31: the wide table's kernels at the Epsilon cell's shape ------
EPSILON = dict(n=400_000, f=2000, b=256)


def test_sliced_histogram_compiles_for_v5e(one_chip):
    """``histogram_segment`` at 2,000 columns: sixteen grid steps of
    one Mosaic body, a dynamic 128-aligned column offset in the DMA."""
    from lightgbm_tpu.ops.hist_pallas import (histogram_segment,
                                              matrix_cols, matrix_rows)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    n, f, b = (EPSILON[k] for k in "nfb")
    i32 = sds((), jnp.int32)
    jax.jit(functools.partial(
        histogram_segment, num_bins=b, num_features=f)).lower(
        sds((matrix_rows(n), matrix_cols(f)), jnp.uint8), i32,
        i32).compile()


def test_wide_partition_compiles_for_v5e(one_chip):
    """``partition_segment`` over the cell's 2,048-byte rows."""
    from lightgbm_tpu.ops.hist_pallas import matrix_cols, matrix_rows
    from lightgbm_tpu.ops.partition_pallas import partition_segment
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    n, f = EPSILON["n"], EPSILON["f"]
    mat = sds((matrix_rows(n), matrix_cols(f)), jnp.uint8)
    i32 = sds((), jnp.int32)
    jax.jit(functools.partial(
        partition_segment, blk=512, use_lut_path=False)).lower(
        mat, mat, *([i32] * 9), sds((1, 256), jnp.float32)).compile()


def test_feature_blocked_scan_compiles_for_v5e(one_chip):
    """The scan of both children at 2,000 x 256."""
    from lightgbm_tpu.ops.split_scan_pallas import _scan_call
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    f, b = EPSILON["f"], EPSILON["b"]
    _, _, params = _scan_args(f=8)
    plane = sds((2, f, b), jnp.float32)
    jax.jit(jax.vmap(functools.partial(
        _scan_call, params=params, interpret=False),
        in_axes=(0, None, None, 0, 0, 0))).lower(
        sds((2, 1, 5), jnp.float32), sds((f, 4), jnp.int32),
        sds((f, 2), jnp.float32), plane, plane, plane).compile()


@pytest.mark.parametrize("f,categorical", [(40, 10), (2000, 0)],
                         ids=["expo-40-categorical", "epsilon-2000"])
def test_histogram_cache_is_written_in_place_on_v5e(one_chip, monkeypatch,
                                                    f, categorical):
    """The per-phase body's two writes into the per-leaf histogram
    cache ``[255, F, B, 3]`` (``lgbm.grow.splits.cache``), in the fused
    block as compiled for the v5e under the chip's plan: two
    ``dynamic-update-slice`` on the carried buffer and no copy of it.
    Without the ``optimization_barrier`` before the writes the
    sibling's subtraction fuses into them and the compiler copies the
    whole cache a write (1.57 GB at 2,000 columns; PERF.md, PR 31)."""
    import re

    import lightgbm_tpu.learner.split_step as split_step
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.models.gbdt import GBDT, _fused_iter_block

    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    rng = np.random.RandomState(0)
    x = rng.randn(2048, f).astype(np.float32)
    x[:, :categorical] = rng.randint(0, 200, (2048, categorical))
    y = (x[:, 4] % 2 + x[:, 12] > 0.5).astype(np.float64)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 255, "verbosity": -1})
    ds = Dataset.from_numpy(x, cfg, label=y,
                            categorical_features=list(range(categorical)))
    b = GBDT(cfg, ds)
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    assert ln.split_plan().body == "per_phase" and ln.cache_hists
    sds = lambda a: jax.ShapeDtypeStruct(               # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    compiled = jax.jit(
        functools.partial(_fused_iter_block, learner=ln,
                          grad_fn=b._grad_fn, bag_fn=None,
                          valid_data=(), k=1),
        static_argnames=("m",)).lower(
        sds(ln.mat), sds(ln.ws), sds(b.train_score), (),
        sds(jnp.float32(0.1)), sds(jnp.int32(0)), m=2).compile()
    cache = r"f32\[255,%d,%d,3\]" % (f, ln.num_bins_max)
    text = compiled.as_text()
    writes = re.findall(r"= %s\S* dynamic-update-slice\(" % cache, text)
    copies = re.findall(r"= %s\S* copy\(" % cache, text)
    assert len(writes) >= 2 and not copies, (len(writes), copies)
    # temporaries hold the cache once, not a second copy of it
    cache_bytes = 255 * f * ln.num_bins_max * 3 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * cache_bytes + (64 << 20)


def test_bundled_grow_program_compiles_for_v5e(one_chip, monkeypatch):
    """The fused block of a bundled one-hot table at the benchmark's
    widths (``allstate-12m-train``: 4,228 logical features in 47 byte
    columns, 255 leaves), compiled for the v5e under the chip's plan:
    the table partition, the group histogram over 47 columns, the
    debundle to 4,228 per-feature histograms under its two scopes, the
    Pallas scan over 34 feature blocks. The per-leaf cache stays in
    group layout (``[255, 47, 256, 3]``, 37 MB) and is written in
    place, never copied; the ``[F, 256, 3]`` expansion is made a split
    and is no part of the loop's carry."""
    import re

    import lightgbm_tpu.learner.split_step as split_step
    from benchmarks.generators.allstate_like import CARDS, NUMERIC
    from benchmarks.kinds.train_sparse import _probe_table
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.learner.partitioned import PartitionedTreeLearner
    from lightgbm_tpu.models.gbdt import GBDT, _fused_iter_block
    from lightgbm_tpu.observability import scopes

    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    x, y = _probe_table(NUMERIC + sum(CARDS), NUMERIC, list(CARDS))
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 255, "verbosity": -1,
        "min_data_in_bin": 1, "feature_pre_filter": False})
    ds = Dataset.from_scipy(x, cfg, label=y)
    b = GBDT(cfg, ds)
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    f, g, bins = ln.num_features, ln.num_groups, ln.num_bins_max
    assert (f, g, bins) == (4228, 47, 256)
    plan = ln.split_plan()
    assert plan.body == "per_phase" and plan.scan_kernel \
        and plan.lut_partition and not plan.cat_scan
    sds = lambda a: jax.ShapeDtypeStruct(               # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    compiled = jax.jit(
        functools.partial(_fused_iter_block, learner=ln,
                          grad_fn=b._grad_fn, bag_fn=None,
                          valid_data=(), k=1),
        static_argnames=("m",)).lower(
        sds(ln.mat), sds(ln.ws), sds(b.train_score), (),
        sds(jnp.float32(0.1)), sds(jnp.int32(0)), m=1).compile()
    text = compiled.as_text()
    cache = r"f32\[255,%d,%d,3\]" % (g, bins)
    writes = re.findall(r"= %s\S* dynamic-update-slice\(" % cache, text)
    copies = re.findall(r"= %s\S* copy\(" % cache, text)
    assert len(writes) >= 2 and not copies, (len(writes), copies)
    # both debundle scopes own instructions; the scan is a Mosaic call
    table = scopes.parse_hlo_scopes(text)
    assert set(scopes.BUNDLE_SCOPES) <= set(table.values())
    assert "tpu_custom_call" in text
    # no while loop carries a buffer with a logical-feature axis
    for carry in re.findall(r"= (\([^\n]*?\)) while\(", text):
        assert not re.search(r"\[(?:2,)?%d,%d(?:,3)?\]" % (f, bins),
                             carry), carry[:200]
    cache_bytes = 255 * g * bins * 3 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * cache_bytes + (64 << 20)


def test_fused_lambdarank_block_compiles_for_v5e(one_chip, monkeypatch):
    """The fused block of the learning-to-rank cell at its real size
    (``msltr-2m-train``: 2,270,296 x 137 in 18,919 query groups of 1 to
    1,251 documents, 255 leaves, steps of 2 trees), compiled for the
    v5e under the chip's plan: the megakernel at 137 columns and
    256-byte rows, and lambdarank's gradients on the query layout
    (``objective/rank.py``). The layout reaches the program as
    ARGUMENTS: the program's text does not grow with the table. No
    gather or scatter of the gradient program has an index a slot: the
    queries come in as windows (an index a query), the order by sorts
    with payload, and the one pass with an index a document is the way
    back. The learner is built on a 2,048-row table and told the row
    count; the matrix is a shape."""
    import re

    import lightgbm_tpu.learner.split_step as split_step
    from benchmarks.generators.msltr_like import query_sizes
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Dataset
    from lightgbm_tpu.data.dataset import Metadata
    from lightgbm_tpu.learner.partitioned import (HIST_BLK,
                                                  PartitionedTreeLearner)
    from lightgbm_tpu.models.gbdt import _fused_iter_block
    from lightgbm_tpu.objective.rank import LambdarankNDCG
    from lightgbm_tpu.observability import scopes
    from lightgbm_tpu.ops.hist_pallas import matrix_cols, matrix_rows

    n, f = 2_270_296, 137
    monkeypatch.setattr(split_step, "on_tpu", lambda: True)
    rng = np.random.RandomState(0)
    cfg = Config.from_params({"objective": "lambdarank",
                              "num_leaves": 255, "verbosity": -1})
    ds = Dataset.from_numpy(rng.randn(2048, f).astype(np.float32), cfg,
                            label=rng.randint(0, 5, 2048).astype(float),
                            group=[2048])
    ln = PartitionedTreeLearner(ds, cfg, interpret=False)
    assert ln.split_plan() == split_step.SplitStepPlan(
        "megakernel", True, False, False)
    ln.num_data = n
    ln._ones_rows = jnp.ones((n,), jnp.float32)
    md = Metadata(n)
    md.set_label(rng.randint(0, 5, n).astype(np.float32))
    sizes = query_sizes(n)
    md.set_query(sizes)
    obj = LambdarankNDCG(cfg)
    obj.init(md, n)
    lay = obj.layout
    assert lay.num_queries == 18_919 and len(lay.lengths) <= 8
    assert lay.slots <= 1.6 * n
    assert lay.pair_slots <= 4 * lay.doc_pairs
    sds = lambda a: jax.ShapeDtypeStruct(               # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    ops = jax.tree.map(sds, obj.grad_operands())
    # nothing the program is handed has an entry a slot or a row of
    # nq x max_query
    assert max(a.size for a in jax.tree.leaves(ops)) == n
    mat = jax.ShapeDtypeStruct(
        (matrix_rows(n, HIST_BLK), matrix_cols(f)), jnp.uint8,
        sharding=one_chip)
    assert mat.shape[1] == 256
    lowered = jax.jit(
        functools.partial(_fused_iter_block, learner=ln,
                          grad_fn=obj.gradients, bag_fn=None,
                          valid_data=(), k=1),
        static_argnames=("m",)).lower(
        mat, mat, jax.ShapeDtypeStruct((n, 1), jnp.float32,
                                       sharding=one_chip), (),
        sds(jnp.float32(0.1)), sds(jnp.int32(0)), ops, m=2)
    # 0.8 MB as lowered (my reading, PR 37); a layout baked in as
    # constants would be 8 hex characters an element: 18 MB a
    # document-sized array, 190 MB for the padded layout's three
    assert len(lowered.as_text()) < 4 << 20
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(text) < 8 << 20                     # 1.9 MB (PR 37)
    mem = compiled.memory_analysis()
    print(mem)
    # two 581 MB matrices in and out, donated by the driver; the
    # temporaries held 618 MB (PR 37): the packed gradients, the pair
    # block's chunk, the query layout's blocks
    assert mem.argument_size_in_bytes < 1.3e9
    assert mem.temp_size_in_bytes < 1.0e9
    table = scopes.parse_hlo_scopes(text)
    assert set(scopes.RANK_SCOPES) <= set(table.values())
    assert "tpu_custom_call" in text
    # every gather of the gradient program as compiled (the windows are
    # no gather any more: the TPU's compiler makes them a loop of
    # slices): the indices it reads, its output's elements over its
    # slice's, are an entry a query or a document, never a slot; it
    # holds no scatter at all
    rank = {name for name, scope in table.items()
            if scope in scopes.RANK_SCOPES or scope == scopes.GRADIENTS}
    seen = 0
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = [a-z]\d+\[([\d,]*)\]"
                         r"\S* (gather|scatter)\(", line)
        if found is None or found.group(1) not in rank:
            continue
        assert found.group(3) == "gather", line[:300]
        seen += 1
        out = np.prod([int(d) for d in found.group(2).split(",")])
        window = np.prod([int(d) for d in re.search(
            r"slice_sizes=\{([\d,]*)\}", line).group(1).split(",")])
        assert out // window <= n < lay.slots, line[:300]
    assert seen >= 1
