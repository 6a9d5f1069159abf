"""The training score and labels kept in the matrix's rows
(models/gbdt.py ``RowScore``): on the fused path of the one-chip
partitioned learner the gradients are made where the row sits and each
tree's leaf values are added by position, with no gather of the
gradients and no scatter-add of the scores. The trees and the
row-order ``train_score`` must be bit for bit what the gather path
gives; everything the rows cannot carry takes the gather path and says
so in ``learner.score_in_row_refused``."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.data import Dataset
from lightgbm_tpu.io.model_text import save_model_to_string
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.models.variants import create_boosting
from lightgbm_tpu.observability.telemetry import get_telemetry
from lightgbm_tpu.ops.hist_pallas import row_words

ROWS = 400
TRACES = "learner.score_in_row_traces"
REFUSED = "learner.score_in_row_refused"


def _table(f, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(ROWS, f).astype(np.float32)
    logit = x[:, 0] + x[:, 1] * x[:, 2] + 0.3 * rng.randn(ROWS)
    return x, logit, rng


def _booster(monkeypatch, params, x, y, *, path="rows", **data):
    """A booster on ``path``: ``rows`` the fused block with the score
    in the rows, ``fused`` the fused block on the gather path (forced
    on a table the rows could carry), ``iterations`` the per-iteration
    route, on the gather path too, whose grow programs every booster of
    one width shares; ``monkeypatch.undo()`` after its training."""
    monkeypatch.setenv("LGBM_TPU_FUSE_ITERS",
                       "0" if path == "iterations" else "1")
    if path == "fused":
        monkeypatch.setattr(gbdt_mod.RowScore, "fits",
                            staticmethod(lambda *a, **k: False))
    cfg = Config.from_params({
        "num_leaves": 5, "learning_rate": 0.1, "max_bin": 31,
        "min_data_in_leaf": 10, "tree_learner": "partitioned",
        "verbosity": -1, "metric": "", **params})
    return create_boosting(cfg, Dataset.from_numpy(x, cfg, label=y,
                                                   **data))


def _train(monkeypatch, params, x, y, steps, *, path, **data):
    """Trained to each of ``steps`` in turn; a callable among them runs
    on the booster there."""
    try:
        b = _booster(monkeypatch, params, x, y, path=path, **data)
        for step in steps:
            step(b) if callable(step) else b.train(step)
        b.finalize_trees()
        return b
    finally:
        monkeypatch.undo()


def _counts(fn):
    tel = get_telemetry()
    tel.ensure_ring()
    before = {k: tel.counters.get(k, 0) for k in (TRACES, REFUSED)}
    out = fn()
    return out, {k: tel.counters.get(k, 0) - v for k, v in before.items()}


def _both(monkeypatch, params, x, y, steps, gather="iterations", **data):
    """A booster on the ``gather`` path and one on the rows, trained
    alike: the same trees bit for bit and the same row-order score."""
    b0 = _train(monkeypatch, params, x, y, steps, path=gather, **data)
    b1, seen = _counts(lambda: _train(monkeypatch, params, x, y, steps,
                                      path="rows", **data))
    assert b0.iter == b1.iter and len(b0.models) == len(b1.models)
    assert save_model_to_string(b0) == save_model_to_string(b1)
    np.testing.assert_array_equal(np.asarray(b0.train_score),
                                  np.asarray(b1.train_score))
    assert seen[REFUSED] == 0 and seen[TRACES] > 0, seen
    return b1


OBJECTIVES = {
    "binary": ({"objective": "binary"}, "class", False),
    "regression": ({"objective": "regression"}, "value", False),
    "binary_unbalance": ({"objective": "binary", "is_unbalance": True},
                         "class", False),
    "binary_weights": ({"objective": "binary"}, "class", True),
}


@pytest.mark.parametrize("features", [28, 67])
@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_rows_match_the_gather_path(monkeypatch, objective, features):
    """The sync first iteration, then two 4-tree blocks, against the
    per-iteration route."""
    params, kind, weighted = OBJECTIVES[objective]
    x, logit, rng = _table(features)
    y = (logit > 0.4).astype(np.float32) if kind == "class" else logit
    w = rng.uniform(0.5, 2.0, ROWS).astype(np.float32) if weighted \
        else None
    b = _both(monkeypatch, params, x, y, (1, 5, 9), weight=w)
    # the score and each per-row operand a word: binary's label value
    # and class weight (and the weight), regression's label
    assert b._fused_rows.words == 1 + (
        1 if objective == "regression" else 2 + weighted)


@pytest.mark.parametrize("gather,steps", [
    ("fused", (1, 5)), ("iterations", (2, 6, 70))],
    ids=["fused_blocks_of_4", "blocks_of_1_4_64"])
def test_rows_match_the_gather_path_by_block(monkeypatch, gather, steps):
    """Against the fused block's own gather path (a 4-tree block), and
    in blocks of 1, 4 and 64 trees."""
    x, logit, _ = _table(28, seed=5)
    y = (logit > 0.4).astype(np.float32)
    b = _both(monkeypatch, {"objective": "binary"}, x, y, steps,
              gather=gather)
    assert b.iter == steps[-1]


def test_the_row_order_score_survives_what_comes_between_blocks(
        monkeypatch):
    """An init score, then between 4-tree blocks: a write of
    ``train_score``, a rollback, a refit, a non-fused iteration. Each
    makes the row-order array the truth, and the next block's rows
    take it."""
    x, logit, rng = _table(28, seed=1)
    y = (logit > 0.4).astype(np.float32)

    def write(b):
        b.train_score = b.train_score + 0.25

    def refit(b):
        b.finalize_trees()
        binned = np.asarray(b.train_data.binned)
        b.refit(np.stack([t.predict_leaf_index_binned(binned)
                          for t in b.models], axis=1))

    steps = (1, 5, write, 9, lambda b: b.rollback_one_iter(), 12, refit,
             16, lambda b: b.train_one_iter(), 21)
    b = _both(monkeypatch, {"objective": "binary"}, x, y, steps,
              init_score=rng.randn(ROWS))
    assert b.iter == 21


def _traced(monkeypatch, params, x, y, **data):
    """The booster's fused block traced, not compiled: which way its
    score goes is counted where the block enters the trace."""
    def trace():
        try:
            b = _booster(monkeypatch, params, x, y, **data)
            b._fused_block().trace(*b._fused_block_args(), m=1)
            return b
        finally:
            monkeypatch.undo()
    return _counts(trace)


@pytest.mark.parametrize("objective,features", [
    ("binary", 103), ("binary", 104), ("binary", 115),
    ("regression", 107), ("regression", 108)])
def test_a_row_without_room_takes_the_gather_path(monkeypatch, objective,
                                                  features):
    """A row has ``128 - (F + 13) % 128`` bytes spare: binary's score
    and two label words need 12 (F % 128 up to 103), regression's score
    and label 8 (up to 107)."""
    x, logit, _ = _table(features)
    y = (logit > 0.4).astype(np.float32)
    b, seen = _traced(monkeypatch, {"objective": objective}, x, y)
    fits = features in (103, 107)
    assert (row_words(features) >= (3 if objective == "binary" else 2)) \
        is fits
    assert (b._fused_rows is not None) is fits
    assert seen == ({TRACES: 1, REFUSED: 0} if fits
                    else {TRACES: 0, REFUSED: 1})


def _mesh_learner(monkeypatch):
    """The data-parallel mesh learner on a one-device mesh, through the
    factory seam (the CPU factory never makes it)."""
    import lightgbm_tpu.parallel as par
    from lightgbm_tpu.parallel.learners import MeshPartitionedTreeLearner
    from lightgbm_tpu.parallel.partition_rules import default_mesh

    def force_mesh(lt, ds, cfg, mesh=None, hist_method="auto"):
        return MeshPartitionedTreeLearner(ds, cfg, mesh=default_mesh(1),
                                          mode="data", interpret=True)
    monkeypatch.setattr(par, "create_tree_learner", force_mesh)
    return {"tree_learner": "data"}


@pytest.mark.parametrize("case", ["bagging", "goss", "lambdarank",
                                  "multiclass", "mesh"])
def test_what_the_rows_cannot_carry_traces_the_gather_path(monkeypatch,
                                                          case):
    """A sampler draws by row, not by position (the rows would bag other
    rows than the gather path's); a ranking objective reads its query's
    rows; multiclass grows k trees an iteration; the mesh learner's
    score is replicated."""
    x, logit, _ = _table(28, seed=4)
    params = {"objective": "binary"}
    y = (logit > 0.4).astype(np.float32)
    data = {}
    if case == "bagging":
        params.update(bagging_fraction=0.6, bagging_freq=1)
    elif case == "goss":
        params.update(boosting="goss")
    elif case == "lambdarank":
        params = {"objective": "lambdarank"}
        y = np.clip(np.round(logit + 1.5), 0, 4).astype(np.float32)
        data["group"] = np.full(ROWS // 20, 20)
    elif case == "multiclass":
        params = {"objective": "multiclass", "num_class": 3}
        y = np.digitize(logit, [-0.5, 0.5]).astype(np.float32)
    else:
        params.update(_mesh_learner(monkeypatch))
    b, seen = _traced(monkeypatch, params, x, y, **data)
    if case == "mesh":
        assert type(b.learner).__name__ == "MeshPartitionedTreeLearner"
    assert b._fused_rows is None
    assert seen == {TRACES: 0, REFUSED: 1}
