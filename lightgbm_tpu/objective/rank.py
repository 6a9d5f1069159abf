"""Ranking objectives: lambdarank and rank_xendcg.

Reference analog: ``src/objective/rank_objective.hpp:98-330``. The
reference loops per query with OpenMP and walks all document pairs
serially. Here the queries are grouped into a handful of LENGTH
CLASSES (``QueryLayout``): a class of length ``L`` holds every query
of more than the class below's and at most ``L`` documents as one
dense ``[nq_c, L]`` block with static shapes, so the slots follow the
documents and the pair slots the pairs the queries hold, not the
longest query (docs/ARCHITECTURE.md, "The query layout").

* Into the layout: a query's documents are contiguous rows, so a
  query's scores (and its labels, which ride in the same operand) are
  ONE window of ``L`` rows from the query's first row
  (``dynamic_slice`` under ``vmap``: a gather of whole windows, one
  index a query); the window's tail past the query's own documents is
  masked.
* Order within a query: one stable multi-operand ``lax.sort`` a class
  with the label and the position as payload, undone by a second sort
  on the position. No ``argsort`` + ``take_along_axis``.
* Pairs: the ``[C, L, L]`` block of ``_lambdarank_pairs``, evaluated in
  bounded-memory chunks of ``C`` queries by ``lax.map``, ``C`` set by
  the class's own ``L``.
* Back to row order: one gather over the documents
  (``slot_of_row``), the only operation with an index a document.

The layout's arrays reach the compiled programs as ARGUMENTS
(``grad_operands``), never as constants: a program's text does not
grow with the table.

Semantic deviations (documented):
  * the reference quantizes the sigmoid into a 2^20-entry lookup table
    (rank_objective.hpp:244-258); we evaluate it exactly — metric-level
    parity is unaffected.
  * rank_xendcg's per-query xorshift streams (rank_objective.hpp:303)
    become one numpy RandomState stream over all docs per iteration —
    the distribution is identical, the stream interleaving is not.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.dataset import Metadata
from ..observability import scopes
from ..observability.telemetry import get_telemetry
from ..utils.jit_registry import register_dynamic
from ..utils.log import log_fatal
from .base import ObjectiveFunction

kEpsilon = 1e-15
kMinScore = -jnp.inf


def default_label_gain() -> np.ndarray:
    """DCGCalculator::DefaultLabelGain (dcg_calculator.cpp:33-41):
    gain[i] = 2^i - 1, capped at 31 labels."""
    return np.asarray([0.0] + [float((1 << i) - 1) for i in range(1, 31)])


def resolve_label_gain(config: Config) -> np.ndarray:
    if config.label_gain:
        return np.asarray(config.label_gain, np.float64)
    return default_label_gain()


def check_rank_labels(label: np.ndarray, num_gain: int) -> None:
    """DCGCalculator::CheckLabel (dcg_calculator.cpp:155-171)."""
    if np.abs(label - np.round(label)).max(initial=0.0) > kEpsilon:
        log_fatal("label should be int type for ranking task, for the "
                  "gain of label, please set the label_gain parameter")
    if label.min(initial=0.0) < 0:
        log_fatal("Label should be non-negative for ranking task")
    if int(label.max(initial=0)) >= num_gain:
        log_fatal(f"Label {int(label.max())} is not less than the number "
                  f"of label mappings ({num_gain})")


def max_dcg_at_k(k: int, labels: np.ndarray, gain: np.ndarray,
                 discount: np.ndarray) -> float:
    """DCGCalculator::CalMaxDCGAtK (dcg_calculator.cpp:54-80): ideal DCG
    = labels sorted descending, gains dotted with discounts."""
    k = min(k, len(labels))
    top = np.sort(labels.astype(np.int64))[::-1][:k]
    return float((gain[top] * discount[:k]).sum())


# a class's pair block [C, L, L] holds at most this many pair slots
PAIR_BLOCK = 1 << 22
# at most this many length classes: each one is two sorts and a pair
# block of its own in the compiled program
MAX_CLASSES = 8
# what a slot costs beside a pair slot when the class lengths are
# chosen: two sorts at 0.8-2 ns an element against 0.02-0.06 ns a pair
# slot, as timed alone on a v5e (PERF.md section 6, PR 37, step 0)
SLOT_COST = 64


def class_lengths(counts: np.ndarray) -> List[int]:
    """The lengths of the classes for queries of ``counts`` documents:
    at most ``MAX_CLASSES`` of the candidates 8, 16, 32, 64, 96, 128,
    192, 256, ... (powers of two and, from 64 up, their halves between)
    with the longest query's own length rounded up to a tile as the
    last, chosen so that ``sum(nq_c * (L_c^2 + SLOT_COST * L_c))`` is
    least (a dynamic programme over the candidates; a query goes to the
    shortest class that holds it)."""
    longest = int(counts.max())
    tile = 128 if longest > 128 else 8
    top = -(-longest // tile) * tile
    cand = sorted({c for k in range(3, 31) for c in
                   ((1 << k), 3 << (k - 1) if k >= 6 else 1 << k)
                   if c < top} | {top})
    # queries of at most cand[j] documents
    ordered = np.sort(counts)
    upto = np.searchsorted(ordered, cand, side="right")
    cost = [c * c + SLOT_COST * c for c in cand]
    n = len(cand)
    inf = float("inf")
    # best[k][j]: least cost of the queries up to cand[j] in k + 1
    # classes of which cand[j] is the longest
    best = [[inf] * n for _ in range(MAX_CLASSES)]
    back = [[-1] * n for _ in range(MAX_CLASSES)]
    for j in range(n):
        best[0][j] = int(upto[j]) * cost[j]
    for k in range(1, MAX_CLASSES):
        for j in range(n):
            for i in range(j):
                c = best[k - 1][i] + int(upto[j] - upto[i]) * cost[j]
                if c < best[k][j]:
                    best[k][j], back[k][j] = c, i
    k = min(range(MAX_CLASSES), key=lambda k: best[k][n - 1])
    j, chosen = n - 1, []
    while j >= 0:
        chosen.append(cand[j])
        j, k = back[k][j], k - 1
    chosen = chosen[::-1]
    # a class of equal cost that holds no query is no class
    held = np.diff(np.searchsorted(ordered, chosen, side="right"),
                   prepend=0)
    return [c for c, h in zip(chosen, held) if h]


class QueryLayout:
    """Ragged query groups as a few dense ``[nq_c, L_c]`` blocks.

    Static (Python) facts: ``lengths`` (``L_c``), ``sizes`` (``nq_c``,
    padded to a whole number of pair chunks with empty queries),
    ``chunks`` (queries a pair block). Device operands (``operands``, a
    pytree the gradient programs take as an argument): per class the
    queries' first rows and document counts, and ``slot_of_row``, the
    slot every document's result is read back from."""

    def __init__(self, query_boundaries: np.ndarray, num_data: int):
        qb = np.asarray(query_boundaries, np.int64)
        counts = np.diff(qb)
        self.num_queries = len(counts)
        self.lengths = class_lengths(counts)
        self.pad = self.lengths[-1]
        cls = np.searchsorted(self.lengths, counts, side="left")
        self.sizes: List[int] = []
        self.chunks: List[int] = []
        self.members: List[np.ndarray] = []   # query ids, data order
        first_slot = np.zeros(self.num_queries, np.int64)
        starts, cnts = [], []
        base = 0
        for c, length in enumerate(self.lengths):
            q = np.flatnonzero(cls == c)
            chunk = max(1, min(len(q), PAIR_BLOCK // (length * length)))
            size = -(-len(q) // chunk) * chunk
            fill = np.zeros(size - len(q), np.int64)
            starts.append(np.concatenate([qb[q], fill]).astype(np.int32))
            cnts.append(np.concatenate([counts[q], fill]).astype(np.int32))
            first_slot[q] = base + np.arange(len(q)) * length
            base += size * length
            self.sizes.append(size)
            self.chunks.append(chunk)
            self.members.append(q)
        self.slots = base
        self.pair_slots = sum(s * l * l for s, l in
                              zip(self.sizes, self.lengths))
        self.doc_pairs = int((counts * counts).sum())
        slot_of_row = np.repeat(first_slot - qb[:-1], counts) \
            + np.arange(num_data)
        self.operands = {
            "starts": tuple(jnp.asarray(s) for s in starts),
            "counts": tuple(jnp.asarray(c) for c in cnts),
            "slot_of_row": jnp.asarray(slot_of_row.astype(np.int32)),
        }

    def per_query(self, values: np.ndarray) -> tuple:
        """A per-query host vector as one device vector a class, zero
        for the empty queries that fill a class up."""
        return tuple(
            jnp.asarray(np.concatenate(
                [values[q], np.zeros(size - len(q), values.dtype)]))
            for q, size in zip(self.members, self.sizes))

    def windows(self, rows: jnp.ndarray, ops) -> list:
        """``rows`` ``[K, num_data]`` (one value a document) as one
        ``[K, nq_c, L_c]`` block a class, with the mask of the slots
        that hold a document: a window of ``L_c`` rows from each
        query's first row."""
        k = rows.shape[0]
        ext = jnp.concatenate(
            [rows, jnp.zeros((k, self.pad), rows.dtype)], axis=1)
        out = []
        for length, starts, counts in zip(self.lengths, ops["starts"],
                                          ops["counts"]):
            win = jax.vmap(lambda s: jax.lax.dynamic_slice(
                ext, (0, s), (k, length)))(starts)       # [nq_c, K, L]
            valid = jnp.arange(length, dtype=jnp.int32)[None, :] \
                < counts[:, None]
            out.append((jnp.moveaxis(win, 1, 0), valid))
        return out

    def to_rows(self, blocks: list, ops) -> jnp.ndarray:
        """One ``[nq_c, L_c, K]`` block a class back to ``[num_data,
        K]`` in row order: the one pass with an index a document (a
        gather of rows of ``K``: 12.6 ms at 2.27 M documents where two
        element gathers take 35.6; PERF.md section 6, PR 37)."""
        k = blocks[0].shape[-1]
        flat = jnp.concatenate([b.reshape(-1, k) for b in blocks])
        return jnp.take(flat, ops["slot_of_row"], axis=0)


class RankingObjective(ObjectiveFunction):
    """RankingObjective (rank_objective.hpp:25-96) on ``QueryLayout``."""

    need_accuracte_prediction = False

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        qb = metadata.query_boundaries
        if qb is None:
            log_fatal("Ranking tasks require query information")
        lay = self.layout = QueryLayout(qb, num_data)
        self.num_queries = lay.num_queries
        # the labels ride into the layout beside the scores
        self._operands = dict(lay.operands, label=jnp.asarray(
            np.asarray(metadata.label, np.float32)), weights=self.weights)
        tel = get_telemetry()
        for name, value in (("queries", lay.num_queries),
                            ("docs", num_data), ("slots", lay.slots),
                            ("pair_slots", lay.pair_slots),
                            ("doc_pairs", lay.doc_pairs),
                            ("classes", len(lay.lengths))):
            tel.set_counter("objective.rank_" + name, value)

    def setup_facts(self) -> dict:
        return {"queries": self.num_queries,
                "classes": len(self.layout.lengths)}

    def _windows(self, score: jnp.ndarray, ops, *more) -> list:
        with jax.named_scope(scopes.RANK_LAYOUT):
            rows = jnp.stack([score.astype(jnp.float32), ops["label"],
                              *more])
            return self.layout.windows(rows, ops)

    def _to_rows(self, blocks: list, ops):
        with jax.named_scope(scopes.RANK_LAYOUT):
            rows = self.layout.to_rows(blocks, ops)
        return self._weighted(rows[:, 0], rows[:, 1], ops)


class LambdarankNDCG(RankingObjective):
    """LambdarankNDCG (rank_objective.hpp:98-260)."""

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        if self.sigmoid <= 0.0:
            log_fatal(f"Sigmoid param {self.sigmoid} should be greater "
                      "than zero")
        self.label_gain = resolve_label_gain(config)

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label, np.float64)
        check_rank_labels(lab, len(self.label_gain))
        lab = lab.astype(np.int64)
        qb = np.asarray(metadata.query_boundaries, np.int64)
        counts = np.diff(qb)
        discount = 1.0 / np.log2(2.0 + np.arange(self.layout.pad))
        # CalMaxDCGAtK of every query at once: the labels sorted
        # descending within their query, the first truncation_level of
        # each dotted with the discounts
        qid = np.repeat(np.arange(self.num_queries), counts)
        order = np.lexsort((-lab, qid))
        rank = np.arange(num_data) - qb[qid]
        top = rank < self.truncation_level
        dcg = np.bincount(
            qid[top], minlength=self.num_queries,
            weights=self.label_gain[lab[order][top]] * discount[rank[top]])
        inv = np.where(dcg > 0, 1.0 / np.maximum(dcg, kEpsilon), 0.0)
        self._operands["inv_max_dcg"] = self.layout.per_query(
            inv.astype(np.float32))
        self._discount = discount.astype(np.float32)
        # the gains of the labels the table holds
        self._gains = [float(g) for g in
                       self.label_gain[:int(lab.max(initial=0)) + 1]]

    def _gradients(self, score: jnp.ndarray, ops):
        lay = self.layout
        blocks = []
        for c, ((rows, valid), inv) in enumerate(zip(
                self._windows(score, ops), ops["inv_max_dcg"])):
            length, chunk = lay.lengths[c], lay.chunks[c]
            with jax.named_scope(scopes.RANK_SORT):
                pos = jnp.broadcast_to(
                    jnp.arange(length, dtype=jnp.int32), valid.shape)
                # descending by score, ties in row order, empty slots
                # last: what a stable argsort of -score gives
                key, lab_s, pos_s = jax.lax.sort(
                    (jnp.where(valid, -rows[0], jnp.inf), rows[1], pos),
                    dimension=1, num_keys=1, is_stable=True)
            with jax.named_scope(scopes.RANK_PAIRS):
                body = functools.partial(
                    _lambdarank_pairs, gains=self._gains,
                    discount=jnp.asarray(self._discount[:length]),
                    sigmoid=self.sigmoid, norm=self.norm)
                lam_s, hess_s = jax.lax.map(
                    lambda t: body(*t),
                    tuple(a.reshape((-1, chunk) + a.shape[1:]) for a in
                          (-key, lab_s, ops["counts"][c], inv)))
            with jax.named_scope(scopes.RANK_SORT):
                _, lam, hess = jax.lax.sort(
                    (pos_s, lam_s.reshape(valid.shape),
                     hess_s.reshape(valid.shape)),
                    dimension=1, num_keys=1)
            blocks.append(jnp.stack([lam, hess], axis=-1))
        return self._to_rows(blocks, ops)

    def name(self) -> str:
        return "lambdarank"


def _lambdarank_pairs(sc_s, lab_s, cnt, inv, *, gains, discount, sigmoid,
                      norm):
    """Pairwise lambdas for a [C, L] chunk of queries sorted by score
    (GetGradientsForOneQuery, rank_objective.hpp:139-230); an empty
    slot's score is ``-inf``."""
    length = sc_s.shape[1]
    slot = jnp.arange(length, dtype=jnp.int32)[None, :]
    valid_s = (slot < cnt[:, None]) & (sc_s > kMinScore)
    best = sc_s[:, 0]
    worst = jnp.max(jnp.where(slot == cnt[:, None] - 1, sc_s, kMinScore),
                    axis=1)
    # the gain of a label, by a select a label the table holds
    gain_s = jnp.zeros_like(sc_s)
    for label, gain in enumerate(gains):
        gain_s = jnp.where(lab_s == label, jnp.float32(gain), gain_s)

    lab_a = lab_s[:, :, None]
    lab_b = lab_s[:, None, :]
    pair_ok = (lab_a > lab_b) & valid_s[:, :, None] & valid_s[:, None, :]

    ds = sc_s[:, :, None] - sc_s[:, None, :]
    gap = gain_s[:, :, None] - gain_s[:, None, :]
    pd = jnp.abs(discount[None, :, None] - discount[None, None, :])
    delta = gap * pd * inv[:, None, None]
    if norm:
        use_norm = (best != worst)[:, None, None]
        delta = jnp.where(use_norm, delta / (0.01 + jnp.abs(ds)), delta)
    sig = 1.0 / (1.0 + jnp.exp(sigmoid * ds))           # GetSigmoid
    p_lambda = jnp.where(pair_ok, -sigmoid * delta * sig, 0.0)
    p_hess = jnp.where(pair_ok,
                       sigmoid * sigmoid * delta * sig * (1.0 - sig), 0.0)

    lam_s = p_lambda.sum(axis=2) - p_lambda.sum(axis=1)
    hess_s = p_hess.sum(axis=2) + p_hess.sum(axis=1)
    if norm:
        sum_lambdas = -2.0 * p_lambda.sum(axis=(1, 2))
        nf = jnp.where(sum_lambdas > 0,
                       jnp.log2(1.0 + sum_lambdas)
                       / jnp.maximum(sum_lambdas, kEpsilon), 1.0)
        lam_s = lam_s * nf[:, None]
        hess_s = hess_s * nf[:, None]
    return lam_s, hess_s


class RankXENDCG(RankingObjective):
    """RankXENDCG (rank_objective.hpp:262-330), arxiv.org/abs/1911.09798."""

    jittable = False  # per-iteration host randomness

    def __init__(self, config: Config):
        super().__init__(config)
        self._rng = np.random.RandomState(config.objective_seed)

    def init(self, metadata: Metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label, np.float64)
        check_rank_labels(lab, 31)

        def xendcg_grad(score, uniforms, ops):
            blocks = [jnp.stack(_xendcg_block(rows[0], rows[2], rows[1],
                                              valid, counts), axis=-1)
                      for (rows, valid), counts in zip(
                          self._windows(score, ops, uniforms),
                          ops["counts"])]
            return self._to_rows(blocks, ops)

        self._grad = register_dynamic("xendcg_grad", jax.jit(xendcg_grad))

    def _gradients(self, score: jnp.ndarray, ops):
        u = self._rng.rand(self.num_data).astype(np.float32)
        return self._grad(score, jnp.asarray(u), ops)

    def name(self) -> str:
        return "rank_xendcg"


def _xendcg_block(s, u, labels, mask, counts):
    """One class's ``[nq_c, L]`` block: scores, uniforms, labels."""
    s = jnp.where(mask, s, -jnp.inf)
    # softmax over valid docs; an empty (padding) query's row is all
    # -inf, whose maximum must not enter the difference
    m = jnp.max(s, axis=1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    rho = e / jnp.maximum(e.sum(axis=1, keepdims=True), kEpsilon)

    phi = jnp.where(mask, jnp.exp2(labels) - u, 0.0)
    sum_labels = jnp.maximum(phi.sum(axis=1, keepdims=True), kEpsilon)
    l1 = jnp.where(mask, -phi / sum_labels + rho, 0.0)
    sum_l1 = l1.sum(axis=1, keepdims=True)

    denom = jnp.maximum(1.0 - rho, kEpsilon)
    l2 = jnp.where(mask, (sum_l1 - l1) / denom, 0.0)
    sum_l2 = l2.sum(axis=1, keepdims=True)
    l3 = jnp.where(mask, (sum_l2 - l2) / denom, 0.0)

    lam_full = l1 + rho * l2 + rho * rho * l3
    single = (counts <= 1)[:, None]
    lam = jnp.where(mask, jnp.where(single, l1, lam_full), 0.0)
    hess = jnp.where(mask, rho * (1.0 - rho), 0.0)
    return lam, hess
