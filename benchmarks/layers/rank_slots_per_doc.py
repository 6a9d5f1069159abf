"""Layer: gradients. Slots of the query layout over the documents it
holds (``objective.rank_slots`` / ``objective.rank_docs``, set once by
the objective's ``init``): 1 would be no padding at all, a layout
padded to the longest query read 10.4 on this table. ``None`` where
the kind hands no ranking counters."""


def read(facts):
    rank = facts.get("rank")
    if not rank or not rank.get("docs"):
        return None
    return rank["slots"] / rank["docs"]
