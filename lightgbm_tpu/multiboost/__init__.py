"""Multiboost: many boosters trained as ONE compiled program.

Public surface:

* :class:`~.batch.BoosterBatch` — B models, one vmapped grow program
  per iteration over a shared Dataset bin layout
* :class:`~.batch.ModelSpec` / :func:`~.batch.bucket_models` — the
  static-shape bucketing layer (what vmaps vs what buckets)
* :func:`~.batch.multiboost_ineligible_reason` — the eligibility
  contract batched training honours byte-for-byte

``engine.train_many`` and ``engine.cv`` are the intended entry
points; constructing a :class:`BoosterBatch` directly is the
low-level API the pipeline's tenant refit loop uses.
"""

from .batch import (BoosterBatch, ModelSpec, MultiboostError,
                    ELIGIBLE_OBJECTIVES, VMAPPED_PARAMS, bucket_key,
                    bucket_models, multiboost_ineligible_reason,
                    multiboost_mode)
from .program import HyperBatch, build_grow_program, mb_score_add

__all__ = [
    "BoosterBatch", "ModelSpec", "MultiboostError", "HyperBatch",
    "ELIGIBLE_OBJECTIVES", "VMAPPED_PARAMS",
    "bucket_key", "bucket_models", "build_grow_program",
    "mb_score_add", "multiboost_ineligible_reason", "multiboost_mode"]
