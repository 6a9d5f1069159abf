"""C API end-to-end: a real C program drives training via the
embedded-CPython shim (native/c_api.cpp + capi_impl.py).

Reference analog: src/c_api.cpp:584-1753 / tests in the reference ride
the Python route; we additionally compile-and-run an actual C client
against native/c_api.h, then verify its outputs (model file,
predictions) from Python.
"""

import ctypes
import os
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "lightgbm_tpu", "native")

pytestmark = pytest.mark.skipif(
    os.environ.get("LGBM_TPU_NO_NATIVE") is not None,
    reason="native disabled")


@pytest.fixture(scope="module")
def capi_so():
    from lightgbm_tpu.native import build_c_api
    so = build_c_api()
    if so is None:
        pytest.skip("no compiler / libpython for the C API shim")
    return so


C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include "c_api.h"

#define CHECK(call) do { \
    if ((call) != 0) { \
        fprintf(stderr, "FAIL %s: %s\n", #call, LGBM_GetLastError()); \
        return 1; \
    } } while (0)

int main(int argc, char** argv) {
    const char* out_dir = argv[1];
    char path[1024];
    int n = 400, f = 5;
    double* data = (double*)malloc(sizeof(double) * n * f);
    float* label = (float*)malloc(sizeof(float) * n);
    /* deterministic pseudo-data: label = [x0 + 0.5*x1 > 0] */
    unsigned s = 42;
    for (int i = 0; i < n; ++i) {
        double x0 = 0, x1 = 0;
        for (int j = 0; j < f; ++j) {
            s = s * 1664525u + 1013904223u;
            double v = ((double)(s >> 8) / (1 << 24)) * 2.0 - 1.0;
            data[i * f + j] = v;
            if (j == 0) x0 = v;
            if (j == 1) x1 = v;
        }
        label[i] = (x0 + 0.5 * x1 > 0) ? 1.0f : 0.0f;
    }

    DatasetHandle ds = NULL;
    CHECK(LGBM_DatasetCreateFromMat(data, C_API_DTYPE_FLOAT64, n, f, 1,
                                    "max_bin=63 verbosity=-1", NULL,
                                    &ds));
    CHECK(LGBM_DatasetSetField(ds, "label", label, n,
                               C_API_DTYPE_FLOAT32));
    int num_data = 0, num_feat = 0;
    CHECK(LGBM_DatasetGetNumData(ds, &num_data));
    CHECK(LGBM_DatasetGetNumFeature(ds, &num_feat));
    printf("dataset %d x %d\n", num_data, num_feat);

    BoosterHandle bst = NULL;
    CHECK(LGBM_BoosterCreate(
        ds, "objective=binary num_leaves=7 learning_rate=0.2 "
            "metric=binary_logloss verbosity=-1", &bst));
    for (int it = 0; it < 8; ++it) {
        int fin = 0;
        CHECK(LGBM_BoosterUpdateOneIter(bst, &fin));
        if (fin) break;
    }
    int cur = 0, ncls = 0, total = 0;
    CHECK(LGBM_BoosterGetCurrentIteration(bst, &cur));
    CHECK(LGBM_BoosterGetNumClasses(bst, &ncls));
    CHECK(LGBM_BoosterNumberOfTotalModel(bst, &total));
    printf("iters=%d classes=%d trees=%d\n", cur, ncls, total);

    int eval_len = 0;
    double evals[16];
    CHECK(LGBM_BoosterGetEvalCounts(bst, &eval_len));
    CHECK(LGBM_BoosterGetEval(bst, 0, &eval_len, evals));
    printf("train_logloss=%.6f\n", evals[0]);

    int64_t out_len = 0;
    double* preds = (double*)malloc(sizeof(double) * n);
    CHECK(LGBM_BoosterPredictForMat(bst, data, C_API_DTYPE_FLOAT64, n,
                                    f, 1, C_API_PREDICT_NORMAL, -1, "",
                                    &out_len, preds));
    printf("npred=%lld p0=%.6f\n", (long long)out_len, preds[0]);

    snprintf(path, sizeof(path), "%s/c_model.txt", out_dir);
    CHECK(LGBM_BoosterSaveModel(bst, 0, -1, path));

    /* round-trip: load the saved model, predict again, same result */
    BoosterHandle bst2 = NULL;
    int it2 = 0;
    CHECK(LGBM_BoosterCreateFromModelfile(path, &it2, &bst2));
    double* preds2 = (double*)malloc(sizeof(double) * n);
    CHECK(LGBM_BoosterPredictForMat(bst2, data, C_API_DTYPE_FLOAT64, n,
                                    f, 1, C_API_PREDICT_NORMAL, -1, "",
                                    &out_len, preds2));
    double maxd = 0;
    for (int i = 0; i < n; ++i) {
        double d = preds[i] - preds2[i];
        if (d < 0) d = -d;
        if (d > maxd) maxd = d;
    }
    printf("loaded_iters=%d roundtrip_maxdiff=%.3g\n", it2, maxd);
    if (maxd > 1e-6) return 1;  /* text-serialized thresholds, same
                                   tolerance as test_model_io */

    /* predictions dump for the Python-side parity check */
    snprintf(path, sizeof(path), "%s/c_preds.txt", out_dir);
    FILE* fh = fopen(path, "w");
    for (int i = 0; i < n; ++i) fprintf(fh, "%.17g\n", preds[i]);
    fclose(fh);
    snprintf(path, sizeof(path), "%s/c_data.txt", out_dir);
    fh = fopen(path, "w");
    for (int i = 0; i < n; ++i) {
        fprintf(fh, "%.17g", (double)label[i]);
        for (int j = 0; j < f; ++j)
            fprintf(fh, "\t%.17g", data[i * f + j]);
        fprintf(fh, "\n");
    }
    fclose(fh);

    CHECK(LGBM_BoosterFree(bst2));
    CHECK(LGBM_BoosterFree(bst));
    CHECK(LGBM_DatasetFree(ds));
    printf("C-DRIVER-OK\n");
    return 0;
}
"""


@pytest.fixture(scope="module")
def c_run(capi_so, tmp_path_factory):
    """Compile + run the C driver once; return its output dir + stdout."""
    tmp = tmp_path_factory.mktemp("capi")
    src = tmp / "driver.c"
    src.write_text(C_DRIVER)
    exe = tmp / "driver"
    subprocess.run(
        ["gcc", "-O1", str(src), "-o", str(exe), f"-I{NATIVE}",
         capi_so, f"-Wl,-rpath,{NATIVE}"],
        check=True, capture_output=True, timeout=120)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_cpu_max_isa" not in flags:
        env["XLA_FLAGS"] = (flags + " --xla_cpu_max_isa=AVX2").strip()
    proc = subprocess.run([str(exe), str(tmp)], env=env,
                          capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return tmp, proc.stdout


def test_c_driver_full_cycle(c_run):
    tmp, out = c_run
    assert "C-DRIVER-OK" in out
    assert "dataset 400 x 5" in out
    assert "classes=1" in out


def test_c_model_loads_in_python_with_identical_predictions(c_run):
    import lightgbm_tpu as lgb
    tmp, _ = c_run
    data = np.loadtxt(tmp / "c_data.txt")
    X = data[:, 1:]
    c_preds = np.loadtxt(tmp / "c_preds.txt")
    bst = lgb.Booster(model_file=str(tmp / "c_model.txt"))
    np.testing.assert_allclose(bst.predict(X), c_preds, rtol=1e-6,
                               atol=1e-9)
    # the C driver trained a real model, not a constant
    y = data[:, 0]
    assert c_preds[y == 1].mean() > c_preds[y == 0].mean() + 0.2


def test_reset_training_data_via_handle_registry():
    """LGBM_BoosterResetTrainingData (round-5 verdict backlog): swap
    the training dataset under the booster handle; the kept trees
    re-seed the new score cache, so continued boosting matches a
    two-stage init_model run on the same data split."""
    from lightgbm_tpu import capi_impl as ci
    rng = np.random.RandomState(3)
    XA = np.ascontiguousarray(rng.randn(300, 4))
    yA = np.ascontiguousarray((XA[:, 0] > 0).astype(np.float32))
    XB = np.ascontiguousarray(rng.randn(260, 4))
    yB = np.ascontiguousarray((XB[:, 0] > 0).astype(np.float32))

    hA = ci.dataset_create_from_mat(
        XA.ctypes.data, ci.DTYPE_FLOAT64, 300, 4, 1, "verbosity=-1", 0)
    ci.dataset_set_field(hA, "label", yA.ctypes.data, 300,
                         ci.DTYPE_FLOAT32)
    b = ci.booster_create(
        hA, "objective=binary num_leaves=7 verbosity=-1 seed=7")
    for _ in range(4):
        ci.booster_update_one_iter(b)

    hB = ci.dataset_create_from_mat(
        XB.ctypes.data, ci.DTYPE_FLOAT64, 260, 4, 1, "verbosity=-1", 0)
    ci.dataset_set_field(hB, "label", yB.ctypes.data, 260,
                         ci.DTYPE_FLOAT32)
    ci.booster_reset_training_data(b, hB)
    # iteration count (trees) survives the swap; training continues
    assert ci.booster_get_current_iteration(b) == 4
    for _ in range(3):
        ci.booster_update_one_iter(b)
    assert ci.booster_get_current_iteration(b) == 7
    assert ci.booster_number_of_total_model(b) == 7

    out = np.zeros(260, np.float64)
    got = ci.booster_predict_for_mat(
        b, XB.ctypes.data, ci.DTYPE_FLOAT64, 260, 4, 1,
        ci.PREDICT_NORMAL, -1, "", out.ctypes.data)
    assert got == 260

    # reference: the same split via the continued-training seed path
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "num_leaves": 7,
              "verbosity": -1, "seed": 7}
    # rebuild stage1 from the SAME booster's first 4 trees (the C
    # route fed f32 labels) to keep the comparison exact
    s = ci.booster_save_model_to_string(b, 0, 4)
    stage1_c = lgb.Booster(model_str=s)
    stage2 = lgb.train(params, lgb.Dataset(
        XB, label=np.asarray(yB, np.float64), free_raw_data=False),
        num_boost_round=3, init_model=stage1_c)
    ref = stage2.predict(XB)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-7)

    # error contract: feature-count mismatch raises cleanly
    X3 = np.ascontiguousarray(rng.randn(50, 3))
    h3 = ci.dataset_create_from_mat(
        X3.ctypes.data, ci.DTYPE_FLOAT64, 50, 3, 1, "verbosity=-1", 0)
    y3 = np.ascontiguousarray(np.zeros(50, np.float32))
    ci.dataset_set_field(h3, "label", y3.ctypes.data, 50,
                         ci.DTYPE_FLOAT32)
    with pytest.raises(Exception, match="features"):
        ci.booster_reset_training_data(b, h3)
    for h in (h3, hB, hA, b):
        ci.free_handle(h)


def test_c_api_error_contract(capi_so):
    """Bad inputs return -1 and set LGBM_GetLastError (never crash)."""
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    out = ctypes.c_void_p()
    rc = lib.LGBM_DatasetCreateFromFile(
        b"/nonexistent/file.csv", b"verbosity=-1", None,
        ctypes.byref(out))
    assert rc == -1
    assert b"" != lib.LGBM_GetLastError()


def test_capi_impl_python_layer_direct(tmp_path):
    """The Python implementation layer works without the C shim (this
    is what the shim calls; covering it directly gives line-accurate
    failures)."""
    from lightgbm_tpu import capi_impl as ci
    rng = np.random.RandomState(0)
    X = np.ascontiguousarray(rng.randn(300, 4))
    y = np.ascontiguousarray(
        (X[:, 0] > 0).astype(np.float32))
    h = ci.dataset_create_from_mat(
        X.ctypes.data, ci.DTYPE_FLOAT64, 300, 4, 1, "verbosity=-1", 0)
    ci.dataset_set_field(h, "label", y.ctypes.data, 300,
                         ci.DTYPE_FLOAT32)
    assert ci.dataset_get_num_data(h) == 300
    assert ci.dataset_get_num_feature(h) == 4
    ci.dataset_set_feature_names(h, ["a", "b", "c", "d"])
    assert ci.dataset_get_feature_names(h) == ["a", "b", "c", "d"]
    addr, n, t = ci.dataset_get_field(h, "label")
    assert n == 300 and t == ci.DTYPE_FLOAT32

    b = ci.booster_create(
        h, "objective=binary num_leaves=7 verbosity=-1")
    for _ in range(5):
        if ci.booster_update_one_iter(b):
            break
    assert ci.booster_get_current_iteration(b) == 5
    assert ci.booster_get_num_classes(b) == 1
    assert ci.booster_calc_num_predict(
        b, 10, ci.PREDICT_LEAF_INDEX, -1) == 50

    out = np.zeros(300, np.float64)
    got = ci.booster_predict_for_mat(
        b, X.ctypes.data, ci.DTYPE_FLOAT64, 300, 4, 1,
        ci.PREDICT_NORMAL, -1, "", out.ctypes.data)
    assert got == 300
    import lightgbm_tpu as lgb
    ref = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1},
                    lgb.Dataset(X, label=np.asarray(y, np.float64)),
                    num_boost_round=5).predict(X)
    # the C route feeds f32 labels (reference label_t is float), the
    # Python route f64 — boost-from-average differs at ~1e-8
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-9)

    s = ci.booster_save_model_to_string(b, 0, -1)
    assert s.startswith("tree\n")
    h2, iters = ci.booster_load_model_from_string(s)
    assert iters == 5
    ci.free_handle(h2)
    ci.free_handle(b)
    ci.free_handle(h)


def test_c_api_csr_train_and_predict(capi_so):
    """CSR ingestion + sparse predict through the compiled shim via
    ctypes: marshalling of the 10/13-arg CSR signatures, sparse
    end-to-end parity with the Python API."""
    sp = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    M = rng.randn(500, 30) * (rng.rand(500, 30) < 0.1)
    M[:, 0] = rng.randn(500)
    y = (M[:, 0] > 0).astype(np.float32)
    csr = sp.csr_matrix(M)
    indptr = np.ascontiguousarray(csr.indptr, np.int32)
    indices = np.ascontiguousarray(csr.indices, np.int32)
    vals = np.ascontiguousarray(csr.data, np.float64)

    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    rc = lib.LGBM_DatasetCreateFromCSR(
        indptr.ctypes.data_as(ctypes.c_void_p), 2,  # INT32
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,    # FLOAT64
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(vals)),
        ctypes.c_int64(30), b"verbosity=-1", None, ctypes.byref(ds))
    assert rc == 0, lib.LGBM_GetLastError()
    yy = np.ascontiguousarray(y)
    assert lib.LGBM_DatasetSetField(
        ds, b"label", yy.ctypes.data_as(ctypes.c_void_p), 500, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=15 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(5):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    out = np.zeros(500, np.float64)
    out_len = ctypes.c_int64()
    rc = lib.LGBM_BoosterPredictForCSR(
        bst, indptr.ctypes.data_as(ctypes.c_void_p), 2,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(indptr)), ctypes.c_int64(len(vals)),
        ctypes.c_int64(30), 0, -1, b"", ctypes.byref(out_len),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0, lib.LGBM_GetLastError()
    assert out_len.value == 500

    # parity: same training through the Python API on the same CSR
    ref = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1},
                    lgb.Dataset(csr, label=np.asarray(y, np.float64)),
                    num_boost_round=5).predict(csr)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-9)
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_importance_and_leaf_values(capi_so):
    """FeatureImportance (split/gain) and leaf get/set through the
    compiled shim; SetLeafValue visibly changes prediction."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = np.ascontiguousarray(rng.randn(300, 6))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    lib.LGBM_BoosterSetLeafValue.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double]
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 300, 6, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 300, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(4):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    imp_split = np.zeros(6, np.float64)
    imp_gain = np.zeros(6, np.float64)
    assert lib.LGBM_BoosterFeatureImportance(
        bst, -1, 0, imp_split.ctypes.data_as(
            ctypes.POINTER(ctypes.c_double))) == 0
    assert lib.LGBM_BoosterFeatureImportance(
        bst, -1, 1, imp_gain.ctypes.data_as(
            ctypes.POINTER(ctypes.c_double))) == 0
    assert imp_split[0] == imp_split.max() > 0   # x0 drives the label
    assert imp_gain[0] == imp_gain.max() > 0

    v = ctypes.c_double()
    assert lib.LGBM_BoosterGetLeafValue(bst, 0, 0,
                                        ctypes.byref(v)) == 0
    assert np.isfinite(v.value)
    assert lib.LGBM_BoosterSetLeafValue(bst, 0, 0, v.value + 1.0) == 0
    v2 = ctypes.c_double()
    assert lib.LGBM_BoosterGetLeafValue(bst, 0, 0,
                                        ctypes.byref(v2)) == 0
    assert abs(v2.value - (v.value + 1.0)) < 1e-12
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_string_out_skips_copy_when_buffer_too_small(capi_so):
    """ADVICE (c_api.cpp copy_string_out): match the reference
    contract — out_len is always the full length incl. NUL, and the
    copy is SKIPPED entirely when it does not fit, never silently
    truncated. Callers probe with a small buffer, then re-call."""
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    rng = np.random.RandomState(9)
    X = np.ascontiguousarray(rng.randn(200, 4))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 200, 4, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 200, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    # probe call: tiny buffer stays untouched, out_len reports the need
    sentinel = b"\xee" * 16
    small = ctypes.create_string_buffer(sentinel, 16)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterSaveModelToString(
        bst, 0, -1, ctypes.c_int64(16), ctypes.byref(out_len),
        small) == 0
    assert out_len.value > 16          # a real model never fits 16 B
    assert small.raw == sentinel       # NOT partially overwritten

    # sized call: full string, NUL-terminated, same reported length
    buf = ctypes.create_string_buffer(out_len.value)
    out_len2 = ctypes.c_int64()
    assert lib.LGBM_BoosterSaveModelToString(
        bst, 0, -1, ctypes.c_int64(out_len.value),
        ctypes.byref(out_len2), buf) == 0
    assert out_len2.value == out_len.value
    text = buf.value.decode()
    assert len(text) == out_len.value - 1
    assert text.startswith("tree") and "Tree=0" in text
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_csc_subset_custom_update_single_row(capi_so):
    """CSC create, row subset, custom-objective update, and single-row
    predict through the compiled shim."""
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(11)
    M = rng.randn(400, 8) * (rng.rand(400, 8) < 0.3)
    M[:, 0] = rng.randn(400)
    y = (M[:, 0] > 0).astype(np.float32)
    csc = sp.csc_matrix(M)
    colptr = np.ascontiguousarray(csc.indptr, np.int32)
    indices = np.ascontiguousarray(csc.indices, np.int32)
    vals = np.ascontiguousarray(csc.data, np.float64)

    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    rc = lib.LGBM_DatasetCreateFromCSC(
        colptr.ctypes.data_as(ctypes.c_void_p), 2,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(colptr)), ctypes.c_int64(len(vals)),
        ctypes.c_int64(400), b"verbosity=-1", None, ctypes.byref(ds))
    assert rc == 0, lib.LGBM_GetLastError()
    yy = np.ascontiguousarray(y)
    assert lib.LGBM_DatasetSetField(
        ds, b"label", yy.ctypes.data_as(ctypes.c_void_p), 400, 0) == 0
    nf = ctypes.c_int()
    assert lib.LGBM_DatasetGetNumFeature(ds, ctypes.byref(nf)) == 0
    assert nf.value == 8

    # row subset aligned with the parent's bins
    idx = np.ascontiguousarray(np.arange(0, 400, 2, dtype=np.int32))
    sub = ctypes.c_void_p()
    rc = lib.LGBM_DatasetGetSubset(
        ds, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), 200,
        b"verbosity=-1", ctypes.byref(sub))
    assert rc == 0, lib.LGBM_GetLastError()
    nd = ctypes.c_int()
    assert lib.LGBM_DatasetGetNumData(sub, ctypes.byref(nd)) == 0
    assert nd.value == 200

    # custom-objective training: hand-rolled logistic grad/hess must
    # reach the same quality direction as the built-in objective
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=custom num_leaves=15 verbosity=-1",
        ctypes.byref(bst)) == 0
    score = np.zeros(400, np.float64)
    import lightgbm_tpu as lgb
    for _ in range(5):
        p = 1.0 / (1.0 + np.exp(-score))
        grad = np.ascontiguousarray((p - y), np.float32)
        hess = np.ascontiguousarray(p * (1 - p), np.float32)
        fin = ctypes.c_int()
        rc = lib.LGBM_BoosterUpdateOneIterCustom(
            bst, grad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            hess.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.byref(fin))
        assert rc == 0, lib.LGBM_GetLastError()
        out_len = ctypes.c_int64()
        lib.LGBM_BoosterPredictForMat(
            bst, np.ascontiguousarray(M).ctypes.data_as(
                ctypes.c_void_p), 1, 400, 8, 1, 1, -1, b"",
            ctypes.byref(out_len),
            score.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    auc_pos = score[y == 1].mean()
    auc_neg = score[y == 0].mean()
    assert auc_pos > auc_neg + 0.5   # custom training really learned

    # single-row predict agrees with the batch row
    row = np.ascontiguousarray(M[3])
    out1 = np.zeros(1, np.float64)
    out_len = ctypes.c_int64()
    rc = lib.LGBM_BoosterPredictForMatSingleRow(
        bst, row.ctypes.data_as(ctypes.c_void_p), 1, 8, 1, 1, -1, b"",
        ctypes.byref(out_len),
        out1.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    assert rc == 0 and out_len.value == 1
    np.testing.assert_allclose(out1[0], score[3], rtol=1e-9)

    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(sub)
    lib.LGBM_DatasetFree(ds)


def test_c_api_network_init_single_machine_noop(capi_so):
    """NetworkInit with one machine is a no-op (like
    init_distributed); NetworkFree is safe uninitialized."""
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    assert lib.LGBM_NetworkInit(b"127.0.0.1:12400", 12400, 1, 1) == 0
    assert lib.LGBM_NetworkFree() == 0


def test_c_api_refit(capi_so):
    """LGBM_BoosterRefit keeps tree structures and refits leaf values
    from supplied leaf assignments over the booster's train data."""
    rng = np.random.RandomState(5)
    X = np.ascontiguousarray(rng.randn(250, 5))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 250, 5, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 250, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(3):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    # leaf assignments of the train rows in every tree
    ntotal = ctypes.c_int()
    assert lib.LGBM_BoosterNumberOfTotalModel(
        bst, ctypes.byref(ntotal)) == 0
    lp = np.zeros(250 * ntotal.value, np.float64)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), 1, 250, 5, 1,
        2, -1, b"", ctypes.byref(out_len),        # LEAF_INDEX
        lp.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    leaf = np.ascontiguousarray(lp.reshape(250, ntotal.value),
                                np.int32)
    v_before = ctypes.c_double()
    assert lib.LGBM_BoosterGetLeafValue(
        bst, 0, 1, ctypes.byref(v_before)) == 0
    rc = lib.LGBM_BoosterRefit(
        bst, leaf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        250, ntotal.value)
    assert rc == 0, lib.LGBM_GetLastError()
    # model still predicts sanely after refit
    out = np.zeros(250, np.float64)
    assert lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), 1, 250, 5, 1, 0, -1,
        b"", ctypes.byref(out_len),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert out[y == 1].mean() > out[y == 0].mean()
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_bound_values(capi_so):
    """Upper/lower bound = sum over trees of extreme leaf outputs
    (gbdt.cpp:631-645); raw predictions must lie within them."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(9)
    X = np.ascontiguousarray(rng.randn(300, 5))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 300, 5, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 300, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(4):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0
    hi = ctypes.c_double()
    lo = ctypes.c_double()
    assert lib.LGBM_BoosterGetUpperBoundValue(bst,
                                              ctypes.byref(hi)) == 0
    assert lib.LGBM_BoosterGetLowerBoundValue(bst,
                                              ctypes.byref(lo)) == 0
    assert lo.value < hi.value
    out = np.zeros(300, np.float64)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), 1, 300, 5, 1,
        1, -1, b"", ctypes.byref(out_len),        # RAW_SCORE
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert out.max() <= hi.value + 1e-9
    assert out.min() >= lo.value - 1e-9
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


THREADED_DRIVER = r"""
#include <pthread.h>
#include <stdio.h>
#include <stdlib.h>
#include "c_api.h"

static BoosterHandle g_bst;
static double* g_X;
static int g_n, g_f;

static void* worker(void* arg) {
    long id = (long)arg;
    double* out = (double*)malloc(sizeof(double) * g_n);
    int64_t out_len = 0;
    for (int rep = 0; rep < 3; ++rep) {
        if (LGBM_BoosterPredictForMat(g_bst, g_X, C_API_DTYPE_FLOAT64,
                                      g_n, g_f, 1, C_API_PREDICT_NORMAL,
                                      -1, "", &out_len, out) != 0) {
            fprintf(stderr, "thread %ld: %s\n", id, LGBM_GetLastError());
            free(out);
            return (void*)1;
        }
    }
    /* also exercise the error path + thread-local last-error */
    DatasetHandle bad = NULL;
    if (LGBM_DatasetCreateFromFile("/nonexistent", "", NULL, &bad)
            != -1) {
        free(out);
        return (void*)1;
    }
    free(out);
    return (void*)0;
}

int main(void) {
    g_n = 200; g_f = 4;
    g_X = (double*)malloc(sizeof(double) * g_n * g_f);
    float* y = (float*)malloc(sizeof(float) * g_n);
    unsigned s = 3;
    for (int i = 0; i < g_n; ++i) {
        for (int j = 0; j < g_f; ++j) {
            s = s * 1664525u + 1013904223u;
            g_X[i * g_f + j] = ((double)(s >> 8) / (1 << 24)) - 0.5;
        }
        y[i] = g_X[i * g_f] > 0 ? 1.0f : 0.0f;
    }
    DatasetHandle ds = NULL;
    if (LGBM_DatasetCreateFromMat(g_X, C_API_DTYPE_FLOAT64, g_n, g_f, 1,
                                  "verbosity=-1", NULL, &ds)) return 1;
    if (LGBM_DatasetSetField(ds, "label", y, g_n, C_API_DTYPE_FLOAT32))
        return 1;
    if (LGBM_BoosterCreate(ds, "objective=binary num_leaves=7 "
                               "verbosity=-1", &g_bst)) return 1;
    int fin = 0;
    if (LGBM_BoosterUpdateOneIter(g_bst, &fin)) return 1;

    /* 4 threads predicting + erroring concurrently: the GIL hand-off,
       mutex-guarded bootstrap and thread-local last-error must hold */
    pthread_t th[4];
    for (long t = 0; t < 4; ++t) pthread_create(&th[t], NULL, worker,
                                                (void*)t);
    long bad = 0;
    for (int t = 0; t < 4; ++t) {
        void* r; pthread_join(th[t], &r); bad += (long)r;
    }
    if (bad) return 1;
    printf("THREADED-OK\n");
    return 0;
}
"""


def test_c_api_threaded_predict(capi_so, tmp_path):
    src = tmp_path / "threaded.c"
    src.write_text(THREADED_DRIVER)
    exe = tmp_path / "threaded"
    subprocess.run(
        ["gcc", "-O1", str(src), "-o", str(exe), f"-I{NATIVE}",
         capi_so, "-lpthread", f"-Wl,-rpath,{NATIVE}"],
        check=True, capture_output=True, timeout=120)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([str(exe)], env=env, capture_output=True,
                          text=True, timeout=570)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}"
    assert "THREADED-OK" in proc.stdout


def test_c_api_merge_shuffle_dump_and_csc_predict(capi_so, tmp_path):
    """Merge (other's trees first), seeded ShuffleModels, dataset text
    dump, and CSC/CSR-single-row prediction through the shim."""
    sp = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(13)
    X = np.ascontiguousarray(rng.randn(200, 4))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    lib.LGBM_BoosterSetLeafValue.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double]

    def make_booster(rounds):
        ds = ctypes.c_void_p()
        assert lib.LGBM_DatasetCreateFromMat(
            X.ctypes.data_as(ctypes.c_void_p), 1, 200, 4, 1,
            b"verbosity=-1", None, ctypes.byref(ds)) == 0
        assert lib.LGBM_DatasetSetField(
            ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 200,
            0) == 0
        bst = ctypes.c_void_p()
        assert lib.LGBM_BoosterCreate(
            ds, b"objective=binary num_leaves=7 verbosity=-1",
            ctypes.byref(bst)) == 0
        fin = ctypes.c_int()
        for _ in range(rounds):
            assert lib.LGBM_BoosterUpdateOneIter(
                bst, ctypes.byref(fin)) == 0
        return ds, bst

    ds1, b1 = make_booster(3)
    ds2, b2 = make_booster(2)
    # make b2's trees distinguishable from b1's (same data + params
    # would otherwise grow identical trees and hide ordering bugs)
    for t in range(2):
        assert lib.LGBM_BoosterSetLeafValue(b2, t, 0,
                                            100.0 + t) == 0

    def leaf0(b, tree):
        v = ctypes.c_double()
        assert lib.LGBM_BoosterGetLeafValue(b, tree,
                                            0, ctypes.byref(v)) == 0
        return v.value

    b1_leaves = [leaf0(b1, t) for t in range(3)]
    assert lib.LGBM_BoosterMerge(b1, b2) == 0
    total = ctypes.c_int()
    assert lib.LGBM_BoosterNumberOfTotalModel(b1,
                                              ctypes.byref(total)) == 0
    assert total.value == 5
    # reference order: OTHER's trees first, then own (gbdt.h:61-79)
    merged = [leaf0(b1, t) for t in range(5)]
    assert merged == [100.0, 101.0] + b1_leaves

    assert lib.LGBM_BoosterShuffleModels(b1, 0, -1) == 0
    assert lib.LGBM_BoosterNumberOfTotalModel(b1,
                                              ctypes.byref(total)) == 0
    assert total.value == 5
    # the permutation must be the reference's seeded Fisher-Yates
    from lightgbm_tpu.utils.ref_random import RefRandom
    idx = list(range(5))
    rng_ref = RefRandom(17)
    for i in range(0, 4):
        j = rng_ref.next_short(i + 1, 5)
        idx[i], idx[j] = idx[j], idx[i]
    assert [leaf0(b1, t) for t in range(5)] == [merged[i] for i in idx]

    dump = str(tmp_path / "dump.txt")
    assert lib.LGBM_DatasetDumpText(ds1, dump.encode()) == 0
    text = open(dump).read()
    assert "num_data: 200" in text and "num_features: 4" in text

    # CSC predict parity with the dense path
    csc = sp.csc_matrix(X)
    colptr = np.ascontiguousarray(csc.indptr, np.int32)
    indices = np.ascontiguousarray(csc.indices, np.int32)
    vals = np.ascontiguousarray(csc.data, np.float64)
    out_csc = np.zeros(200, np.float64)
    out_dense = np.zeros(200, np.float64)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterPredictForCSC(
        b1, colptr.ctypes.data_as(ctypes.c_void_p), 2,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(colptr)), ctypes.c_int64(len(vals)),
        ctypes.c_int64(200), 0, -1, b"", ctypes.byref(out_len),
        out_csc.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert lib.LGBM_BoosterPredictForMat(
        b1, X.ctypes.data_as(ctypes.c_void_p), 1, 200, 4, 1, 0, -1,
        b"", ctypes.byref(out_len),
        out_dense.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    np.testing.assert_array_equal(out_csc, out_dense)

    # CSR single-row forwards to the CSR path
    csr = sp.csr_matrix(X[5:6])
    ip = np.ascontiguousarray(csr.indptr, np.int32)
    ix = np.ascontiguousarray(csr.indices, np.int32)
    v = np.ascontiguousarray(csr.data, np.float64)
    one = np.zeros(1, np.float64)
    assert lib.LGBM_BoosterPredictForCSRSingleRow(
        b1, ip.ctypes.data_as(ctypes.c_void_p), 2,
        ix.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        v.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(ip)), ctypes.c_int64(len(v)),
        ctypes.c_int64(4), 0, -1, b"", ctypes.byref(out_len),
        one.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    np.testing.assert_allclose(one[0], out_dense[5], rtol=1e-12)

    for handle in (b1, b2):
        lib.LGBM_BoosterFree(handle)
    for handle in (ds1, ds2):
        lib.LGBM_DatasetFree(handle)


def test_c_api_streaming_push_ingestion(capi_so):
    """CreateFromSampledColumn + PushRows (+ByCSR) + CreateByReference
    through the compiled shim: with the sample covering every row, the
    streamed dataset must train EXACTLY like the from-mat dataset."""
    sp = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(21)
    n, f = 300, 6
    X = np.ascontiguousarray(rng.randn(n, f))
    X[rng.rand(n, f) < 0.3] = 0.0            # real zeros for EFB stats
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))

    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p

    # per-column nonzero samples over ALL rows (num_sample_row = n)
    col_vals, col_idx = [], []
    for j in range(f):
        nz = np.nonzero(X[:, j] != 0)[0].astype(np.int32)
        col_idx.append(np.ascontiguousarray(nz))
        col_vals.append(np.ascontiguousarray(X[nz, j], np.float64))
    DP = ctypes.POINTER(ctypes.c_double)
    IP = ctypes.POINTER(ctypes.c_int32)
    data_arr = (DP * f)(*[v.ctypes.data_as(DP) for v in col_vals])
    idx_arr = (IP * f)(*[v.ctypes.data_as(IP) for v in col_idx])
    nper = np.ascontiguousarray(
        [len(v) for v in col_vals], np.int32)

    ds = ctypes.c_void_p()
    rc = lib.LGBM_DatasetCreateFromSampledColumn(
        data_arr, idx_arr, f,
        nper.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n, n,
        b"verbosity=-1", ctypes.byref(ds))
    assert rc == 0, lib.LGBM_GetLastError()

    # push in three blocks: dense, dense, CSR
    assert lib.LGBM_DatasetPushRows(
        ds, np.ascontiguousarray(X[:100]).ctypes.data_as(
            ctypes.c_void_p), 1, 100, f, 0) == 0
    assert lib.LGBM_DatasetPushRows(
        ds, np.ascontiguousarray(X[100:200]).ctypes.data_as(
            ctypes.c_void_p), 1, 100, f, 100) == 0
    csr = sp.csr_matrix(X[200:])
    ip = np.ascontiguousarray(csr.indptr, np.int32)
    ix = np.ascontiguousarray(csr.indices, np.int32)
    v = np.ascontiguousarray(csr.data, np.float64)
    assert lib.LGBM_DatasetPushRowsByCSR(
        ds, ip.ctypes.data_as(ctypes.c_void_p), 2,
        ix.ctypes.data_as(IP), v.ctypes.data_as(ctypes.c_void_p), 1,
        ctypes.c_int64(len(ip)), ctypes.c_int64(len(v)),
        ctypes.c_int64(f), ctypes.c_int64(200)) == 0, \
        lib.LGBM_GetLastError()
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), n, 0) == 0

    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(4):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0
    out = np.zeros(n, np.float64)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), 1, n, f, 1, 0, -1,
        b"", ctypes.byref(out_len),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0

    # exact parity with the whole-matrix path (same rows sampled)
    ref = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1},
                    lgb.Dataset(X, label=np.asarray(y, np.float64)),
                    num_boost_round=4).predict(X)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-9)

    # aligned valid set by reference + push
    ds2 = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateByReference(
        ds, ctypes.c_int64(100), ctypes.byref(ds2)) == 0
    assert lib.LGBM_DatasetPushRows(
        ds2, np.ascontiguousarray(X[:100]).ctypes.data_as(
            ctypes.c_void_p), 1, 100, f, 0) == 0
    yv = np.ascontiguousarray(y[:100])
    assert lib.LGBM_DatasetSetField(
        ds2, b"label", yv.ctypes.data_as(ctypes.c_void_p), 100, 0) == 0
    nd = ctypes.c_int()
    assert lib.LGBM_DatasetGetNumData(ds2, ctypes.byref(nd)) == 0
    assert nd.value == 100
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds2)
    lib.LGBM_DatasetFree(ds)


def test_c_api_param_checking_and_predict_for_mats(capi_so):
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    # frozen dataset param changes must be rejected
    assert lib.LGBM_DatasetUpdateParamChecking(
        b"max_bin=255", b"max_bin=63") == -1
    assert b"max_bin" in lib.LGBM_GetLastError()
    assert lib.LGBM_DatasetUpdateParamChecking(
        b"max_bin=255 learning_rate=0.1",
        b"learning_rate=0.2 num_leaves=31") == 0

    rng = np.random.RandomState(6)
    X = np.ascontiguousarray(rng.randn(150, 4))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 150, 4, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 150, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 verbosity=-1",
        ctypes.byref(bst)) == 0
    fin = ctypes.c_int()
    for _ in range(3):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0

    # array-of-row-pointers predict == contiguous predict
    rows = [np.ascontiguousarray(X[i]) for i in range(150)]
    VP = ctypes.c_void_p
    row_ptrs = (VP * 150)(*[r.ctypes.data_as(VP) for r in rows])
    out_ptrs = np.zeros(150, np.float64)
    out_mat = np.zeros(150, np.float64)
    out_len = ctypes.c_int64()
    assert lib.LGBM_BoosterPredictForMats(
        bst, row_ptrs, 1, 150, 4, 0, -1, b"", ctypes.byref(out_len),
        out_ptrs.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert lib.LGBM_BoosterPredictForMat(
        bst, X.ctypes.data_as(ctypes.c_void_p), 1, 150, 4, 1, 0, -1,
        b"", ctypes.byref(out_len),
        out_mat.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    np.testing.assert_array_equal(out_ptrs, out_mat)
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_feature_name_round_trip(capi_so):
    """Set/GetFeatureNames through the caller-allocated char** buffer
    convention (reference GetEvalNames/GetFeatureNames contract)."""
    rng = np.random.RandomState(8)
    X = np.ascontiguousarray(rng.randn(80, 3))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 80, 3, 1,
        b"verbosity=-1 min_data_in_leaf=5", None,
        ctypes.byref(ds)) == 0
    names = (ctypes.c_char_p * 3)(b"alpha", b"beta", b"gamma")
    assert lib.LGBM_DatasetSetFeatureNames(
        ds, ctypes.cast(names, ctypes.POINTER(ctypes.c_char_p)),
        3) == 0
    bufs = [ctypes.create_string_buffer(64) for _ in range(3)]
    out_arr = (ctypes.c_char_p * 3)(
        *[ctypes.cast(b, ctypes.c_char_p) for b in bufs])
    out_len = ctypes.c_int()
    assert lib.LGBM_DatasetGetFeatureNames(
        ds, ctypes.cast(out_arr, ctypes.POINTER(ctypes.c_char_p)),
        ctypes.byref(out_len)) == 0
    assert out_len.value == 3
    assert [b.value for b in bufs] == [b"alpha", b"beta", b"gamma"]

    # names flow into the trained model too
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 80, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=4 verbosity=-1 "
            b"min_data_in_leaf=5", ctypes.byref(bst)) == 0
    bufs2 = [ctypes.create_string_buffer(64) for _ in range(3)]
    out2 = (ctypes.c_char_p * 3)(
        *[ctypes.cast(b, ctypes.c_char_p) for b in bufs2])
    assert lib.LGBM_BoosterGetFeatureNames(
        bst, ctypes.byref(out_len),
        ctypes.cast(out2, ctypes.POINTER(ctypes.c_char_p))) == 0
    assert [b.value for b in bufs2] == [b"alpha", b"beta", b"gamma"]
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(ds)


def test_c_api_group_field_round_trip(capi_so):
    """SetField('group') stores query sizes; GetField returns the
    reference's CUMULATIVE boundaries (metadata.cpp query_boundaries),
    kept alive for the handle's lifetime."""
    rng = np.random.RandomState(15)
    X = np.ascontiguousarray(rng.randn(60, 3))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 60, 3, 1,
        b"verbosity=-1 min_data_in_leaf=5", None,
        ctypes.byref(ds)) == 0
    groups = np.ascontiguousarray([10, 20, 30], np.int32)
    assert lib.LGBM_DatasetSetField(
        ds, b"group", groups.ctypes.data_as(ctypes.c_void_p), 3,
        2) == 0    # INT32
    out_ptr = ctypes.c_void_p()
    out_len = ctypes.c_int()
    out_type = ctypes.c_int()
    assert lib.LGBM_DatasetGetField(
        ds, b"group", ctypes.byref(out_len), ctypes.byref(out_ptr),
        ctypes.byref(out_type)) == 0
    assert out_type.value == 2 and out_len.value == 4
    bounds = np.ctypeslib.as_array(
        ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_int32)), (4,))
    np.testing.assert_array_equal(bounds, [0, 10, 30, 60])
    lib.LGBM_DatasetFree(ds)


def test_c_api_valid_set_eval(capi_so):
    """AddValidData + GetEval(data_idx=1) return the valid metric."""
    rng = np.random.RandomState(17)
    X = np.ascontiguousarray(rng.randn(200, 4))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    Xv = np.ascontiguousarray(rng.randn(80, 4))
    yv = np.ascontiguousarray((Xv[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 200, 4, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 200, 0) == 0
    dv = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        Xv.ctypes.data_as(ctypes.c_void_p), 1, 80, 4, 1,
        b"verbosity=-1", ds, ctypes.byref(dv)) == 0
    assert lib.LGBM_DatasetSetField(
        dv, b"label", yv.ctypes.data_as(ctypes.c_void_p), 80, 0) == 0
    bst = ctypes.c_void_p()
    assert lib.LGBM_BoosterCreate(
        ds, b"objective=binary num_leaves=7 "
            b"metric=binary_logloss verbosity=-1",
        ctypes.byref(bst)) == 0
    assert lib.LGBM_BoosterAddValidData(bst, dv) == 0
    fin = ctypes.c_int()
    for _ in range(3):
        assert lib.LGBM_BoosterUpdateOneIter(bst, ctypes.byref(fin)) == 0
    n_ev = ctypes.c_int()
    evals = np.zeros(8, np.float64)
    assert lib.LGBM_BoosterGetEval(
        bst, 1, ctypes.byref(n_ev),
        evals.ctypes.data_as(ctypes.POINTER(ctypes.c_double))) == 0
    assert n_ev.value == 1
    assert 0.0 < evals[0] < 1.0          # logloss on the valid set
    lib.LGBM_BoosterFree(bst)
    lib.LGBM_DatasetFree(dv)
    lib.LGBM_DatasetFree(ds)


def test_c_api_save_binary(capi_so, tmp_path):
    """DatasetSaveBinary writes the npz cache a Python Dataset loads."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(19)
    X = np.ascontiguousarray(rng.randn(120, 4))
    y = np.ascontiguousarray((X[:, 0] > 0).astype(np.float32))
    lib = ctypes.CDLL(capi_so)
    lib.LGBM_GetLastError.restype = ctypes.c_char_p
    ds = ctypes.c_void_p()
    assert lib.LGBM_DatasetCreateFromMat(
        X.ctypes.data_as(ctypes.c_void_p), 1, 120, 4, 1,
        b"verbosity=-1", None, ctypes.byref(ds)) == 0
    assert lib.LGBM_DatasetSetField(
        ds, b"label", y.ctypes.data_as(ctypes.c_void_p), 120, 0) == 0
    path = str(tmp_path / "ds.bin")
    assert lib.LGBM_DatasetSaveBinary(ds, path.encode()) == 0, \
        lib.LGBM_GetLastError()
    assert os.path.getsize(path) > 0
    # the Python loader reads the binary back with identical content
    loaded = lgb.Dataset(path, params={"verbosity": -1}).construct()
    from lightgbm_tpu import capi_impl as ci
    np.testing.assert_array_equal(
        loaded._inner.binned, ci._get(int(ds.value))._inner.binned)
    np.testing.assert_array_equal(loaded.get_label(), y)
    lib.LGBM_DatasetFree(ds)
