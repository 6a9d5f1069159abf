"""Device-resident boosting loop (ISSUE 2): batched metric eval,
device bagging, per-iteration dispatch/host-sync accounting, and where
the persistent compile cache goes.

Parity tests here pin the bit-compatibility contract: the device-eval
path must produce EXACTLY the host path's metric values (same fetched
bits, same f64 reductions), and device bagging must be deterministic
and identical between its jitted per-iteration form and the traceable
form the fused scan uses.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.observability.telemetry import get_telemetry


def _toy(n=600, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


@pytest.fixture
def tel():
    t = get_telemetry()
    t.reset()
    yield t
    t.reset()


# ---------------------------------------------------------------------
# device-resident metric eval
def _train_with_metrics(monkeypatch, device: bool, params=None):
    monkeypatch.setenv("LGBM_TPU_DEVICE_EVAL", "1" if device else "0")
    X, y = _toy(700)
    Xv, yv = _toy(250, seed=1)
    out = {}
    train_set = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": ["binary_logloss", "auc", "binary_error"],
               **(params or {})},
              train_set, num_boost_round=4,
              valid_sets=[train_set,
                          lgb.Dataset(Xv, label=yv,
                                      reference=train_set)],
              evals_result=out, verbose_eval=False)
    return out


def test_device_eval_bitwise_matches_host_path(monkeypatch):
    """The batched device fetch feeds the SAME host f64 reductions, so
    every recorded metric value must be bit-identical to the legacy
    per-metric fetch path."""
    host = _train_with_metrics(monkeypatch, device=False)
    dev = _train_with_metrics(monkeypatch, device=True)
    assert host.keys() == dev.keys()
    for ds_name in host:
        assert host[ds_name].keys() == dev[ds_name].keys()
        for mname in host[ds_name]:
            assert host[ds_name][mname] == dev[ds_name][mname], \
                (ds_name, mname)


def test_device_eval_bitwise_matches_multiclass(monkeypatch):
    rng = np.random.RandomState(3)
    X = rng.randn(500, 5)
    y = (rng.rand(500) * 3).astype(int).astype(float)

    def run(device):
        monkeypatch.setenv("LGBM_TPU_DEVICE_EVAL",
                           "1" if device else "0")
        out = {}
        ts = lgb.Dataset(X, label=y)
        lgb.train({"objective": "multiclass", "num_class": 3,
                   "num_leaves": 7, "verbosity": -1,
                   "metric": ["multi_logloss", "multi_error"]},
                  ts, num_boost_round=3,
                  valid_sets=[ts], evals_result=out,
                  verbose_eval=False)
        return out

    host, dev = run(False), run(True)
    assert host == dev


def test_gbdt_eval_metrics_batched_matches_legacy(monkeypatch):
    """GBDT.eval_metrics (the CLI/GBDT.train eval seam) — same rows,
    same order, same bits on both paths."""
    X, y = _toy(500, seed=5)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "metric": ["binary_logloss", "auc"],
        "is_provide_training_metric": True})
    ds = Dataset.from_numpy(np.asarray(X, np.float32), cfg,
                            label=np.asarray(y, np.float32))
    b = GBDT(cfg, ds)
    b.train(3)
    monkeypatch.setenv("LGBM_TPU_DEVICE_EVAL", "1")
    dev_rows = b.eval_metrics()
    monkeypatch.setenv("LGBM_TPU_DEVICE_EVAL", "0")
    host_rows = b.eval_metrics()
    assert dev_rows == host_rows
    assert [r[:2] for r in dev_rows] == [("training", "binary_logloss"),
                                         ("training", "auc")]


# ---------------------------------------------------------------------
# device bagging
def _bag_booster(params=None, n=400, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    cfg = Config.from_params({
        "objective": "binary", "num_leaves": 7, "verbosity": -1,
        "metric": "", "bagging_fraction": 0.6, "bagging_freq": 2,
        **(params or {})})
    ds = Dataset.from_numpy(X, cfg, label=y)
    return GBDT(cfg, ds)


def test_device_bagging_stream_properties():
    """The device mask is deterministic in (seed, iteration), honors
    bagging_freq periods, and matches the traceable (fused-scan) form
    bit-for-bit — the fused/per-iteration parity invariant."""
    b = _bag_booster()
    m0 = np.asarray(b._bagging_weight(0))
    b.bag_weight = None
    m1 = np.asarray(b._bagging_weight(1))
    b.bag_weight = None
    m2 = np.asarray(b._bagging_weight(2))
    # freq=2: iterations 0/1 share the draw, 2 re-draws
    np.testing.assert_array_equal(m0, m1)
    assert not np.array_equal(m0, m2)
    assert set(np.unique(m0)) <= {0.0, 1.0}
    frac = m0.mean()
    assert 0.4 < frac < 0.8  # ~bagging_fraction
    # the traceable form (what the fused scan traces) is the same draw
    bag_fn = b._traceable_bag_fn()
    assert bag_fn is not None
    import jax.numpy as jnp
    np.testing.assert_array_equal(
        np.asarray(bag_fn(jnp.int32(1), None, None)), m1)
    np.testing.assert_array_equal(
        np.asarray(bag_fn(jnp.int32(2), None, None)), m2)
    # same seed -> same stream on a fresh booster
    b2 = _bag_booster()
    np.testing.assert_array_equal(np.asarray(b2._bagging_weight(0)), m0)
    # different seed -> different stream
    b3 = _bag_booster({"bagging_seed": 99})
    assert not np.array_equal(np.asarray(b3._bagging_weight(0)), m0)


def test_balanced_bagging_device_mask_respects_fractions():
    b = _bag_booster({"bagging_fraction": 1.0,
                      "pos_bagging_fraction": 0.9,
                      "neg_bagging_fraction": 0.2}, n=2000)
    mask = np.asarray(b._bagging_weight(0))
    label = np.asarray(b.train_data.metadata.label)
    pos_rate = mask[label > 0].mean()
    neg_rate = mask[label <= 0].mean()
    assert 0.8 < pos_rate <= 1.0
    assert 0.1 < neg_rate < 0.35


def test_host_bagging_kill_switch(monkeypatch):
    """LGBM_TPU_HOST_BAG=1 restores the host MT19937 stream (the
    pre-device path) — it must still train and differ from the device
    stream only in WHICH rows are bagged, not in mechanics."""
    monkeypatch.setenv("LGBM_TPU_HOST_BAG", "1")
    b = _bag_booster()
    mask = np.asarray(b._bagging_weight(0))
    assert set(np.unique(mask)) <= {0.0, 1.0}
    b.train(3)
    assert b.num_iterations_trained == 3
    # host bagging must keep the fused path OFF (host RNG in a scan
    # would freeze)
    assert b._traceable_bag_fn() is None


def test_bagged_training_reproducible_and_seeded():
    p1 = _bag_booster({"bagging_seed": 7})
    p1.train(5)
    p2 = _bag_booster({"bagging_seed": 7})
    p2.train(5)
    rng = np.random.RandomState(0)
    X = rng.randn(400, 5).astype(np.float32)
    np.testing.assert_array_equal(p1.predict_raw(X), p2.predict_raw(X))


# ---------------------------------------------------------------------
# dispatch / host-sync accounting
def test_iter_records_carry_dispatch_and_sync_counts(tel):
    tel.configure(summary=False)
    X, y = _toy(500)
    out = {}
    train_set = lgb.Dataset(X, label=y)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metric": "binary_logloss"}, train_set,
              num_boost_round=3,
              valid_sets=[lgb.Dataset(X[:100], label=y[:100],
                                      reference=train_set)],
              evals_result=out, verbose_eval=False)
    iters = [r for r in tel.records if r.get("kind") == "iter"]
    assert len(iters) == 3
    for r in iters:
        counts = r.get("counts") or {}
        assert counts.get("host.dispatches", 0) > 0
    # the device-eval path costs ONE batched sync per eval boundary
    # plus the per-tree host pull; far below the legacy per-metric
    # fetch storm
    total_syncs = sum((r.get("counts") or {}).get("host.syncs", 0)
                      for r in iters)
    assert total_syncs <= 3 * 3  # <= 3 per iteration (tree+eval+flush)


def test_run_report_digest_surfaces_counts(tel, tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel.configure(jsonl_path=path, summary=False)
    X, y = _toy(400)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "verbosity": -1, "metric": "binary_logloss"},
              lgb.Dataset(X, label=y), num_boost_round=2,
              valid_sets=[lgb.Dataset(X, label=y)], verbose_eval=False)
    tel.flush()
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "run_report", os.path.join(repo, "tools", "run_report.py"))
    rr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rr)
    d = rr.digest(rr.load(path))
    assert "host.dispatches" in d["iter_counts"]
    assert d["iter_counts"]["host.dispatches"]["per_iter"] > 0
    text = rr.render(rr.load(path))
    assert "dispatch / host-sync accounting" in text


# ---------------------------------------------------------------------
# where the persistent compile cache goes (utils/compile_cache.py).
# Logic only: flipping the real process-global jax cache inside the
# CPU suite is unsafe, see tests/conftest.py
@pytest.fixture
def cache_rule(monkeypatch):
    import jax

    from lightgbm_tpu.utils import compile_cache as cc
    monkeypatch.setattr(cc, "_STATE", {"enabled_dir": None})
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return cc, calls


def test_compile_cache_env_set_is_left_to_jax(cache_rule, monkeypatch,
                                              tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax owns the directory; the
    module reports it and never writes jax_compilation_cache_dir."""
    cc, calls = cache_rule
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.resolve_cache_dir() == str(tmp_path)
    assert cc.maybe_enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert cc.artifact_dir() == str(tmp_path / "aot")
    assert (tmp_path / "aot").is_dir()


def test_compile_cache_operator_floors_stand(cache_rule, monkeypatch,
                                             tmp_path):
    """The "cache everything" floors are defaults: one the operator set
    through jax's own variable is not overridden; and a cache tree the
    process cannot write gives no artifact dir (host route), not an
    OSError."""
    cc, calls = cache_rule
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                       raising=False)
    cc.maybe_enable_compile_cache()
    assert dict(calls) == {
        "jax_persistent_cache_min_entry_size_bytes": -1}
    (tmp_path / "aot").write_text("a file where the directory goes")
    assert cc.artifact_dir() is None


def test_compile_cache_unset_on_cpu_is_none(cache_rule, monkeypatch):
    cc, calls = cache_rule
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc.resolve_cache_dir() is None
    assert cc.maybe_enable_compile_cache() is None
    assert cc.artifact_dir() is None
    assert calls == []


def test_compile_cache_unset_on_tpu_is_the_fixed_path(
        cache_rule, monkeypatch, tmp_path):
    """Unset on a TPU backend: the fixed <checkout>/.jax_cache_tpu —
    never a temp name, a pid or a time (the path is part of jax's
    cache key)."""
    import inspect
    cc, calls = cache_rule
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cc, "on_tpu", lambda: True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.TPU_CACHE_DIR == os.path.join(repo, ".jax_cache_tpu")
    assert cc.resolve_cache_dir() == cc.TPU_CACHE_DIR
    src = inspect.getsource(cc)
    assert "tempfile" not in src and "getpid" not in src

    # the enable call hands jax that directory once; later calls are
    # latched (redirected here so the test writes nothing in the repo)
    monkeypatch.setattr(cc, "TPU_CACHE_DIR", str(tmp_path / "c"))
    assert cc.maybe_enable_compile_cache() == str(tmp_path / "c")
    assert ("jax_compilation_cache_dir", str(tmp_path / "c")) in calls
    n = len(calls)
    assert cc.maybe_enable_compile_cache() == str(tmp_path / "c")
    assert len(calls) == n
    assert cc.artifact_dir() == str(tmp_path / "c" / "aot")


# ---------------------------------------------------------------------
# dynamic transfer-guard enforcement (tools/graftlint/runtime.py): the
# device-resident contract — no IMPLICIT device->host transfers on the
# training path — is enforced at runtime, not just by counter drift.
# Library-internal fetches (eval boundaries, stop flags, host trees)
# must all be explicit jax.device_get; a reintroduced np.asarray /
# float() / .item() stray coercion raises here and fails tier-1.
def test_training_guarded_against_implicit_host_transfers():
    from tools.graftlint.runtime import no_implicit_host_transfers
    X, y = _toy(700)
    Xv, yv = _toy(250, seed=1)
    out = {}
    train_set = lgb.Dataset(X, label=y)
    valid = lgb.Dataset(Xv, label=yv, reference=train_set)
    with no_implicit_host_transfers():
        # eval-bearing host-stepped loop (device eval, batched fetch)
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1,
                   "metric": ["binary_logloss", "auc", "binary_error"]},
                  train_set, num_boost_round=3,
                  valid_sets=[train_set, valid],
                  evals_result=out, verbose_eval=False)
    assert out["valid_1"]["binary_logloss"]


def test_pipelined_and_bagged_training_guarded():
    from tools.graftlint.runtime import no_implicit_host_transfers
    b = _bag_booster()
    with no_implicit_host_transfers():
        # async/pipelined loop + device bagging: zero implicit syncs
        b.train(4)
    assert b.num_iterations_trained == 4
    rng = np.random.RandomState(0)
    with no_implicit_host_transfers():
        raw = b.predict_raw(rng.randn(50, 5).astype(np.float32))
    assert np.isfinite(np.asarray(raw)).all()


def test_roofline_peaks_are_keyed_by_exact_device_kind():
    from types import SimpleNamespace

    from lightgbm_tpu.utils import LightGBMError
    from lightgbm_tpu.utils.roofline import (bench_roofline,
                                             device_peaks, normalize)
    # the CPU backend of the suite (any kind not in the table) is an
    # error, never an "n/a" row
    with pytest.raises(LightGBMError, match="device_kind='cpu'"):
        device_peaks()
    with pytest.raises(LightGBMError):
        bench_roofline(1e6, 28)
    with pytest.raises(LightGBMError):   # no substring matching
        device_peaks(SimpleNamespace(device_kind="TPU v5",
                                     platform="tpu"))
    v5e = SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    peaks = device_peaks(v5e)
    assert peaks == {"device_kind": "TPU v5 lite", "backend": "tpu",
                     "hbm_gbps": 819.0, "mxu_tflops": 197.0}
    rf = normalize(2e9, 40, peaks)  # 80 GB/s of 819
    assert rf["achieved_gbps"] == 80.0 and rf["hbm_peak_gbps"] == 819.0
    assert abs(rf["hbm_frac"] - 80.0 / 819.0) < 1e-4  # 4-decimal round
    assert json.loads(json.dumps(rf)) == rf
