"""Layer: gradients. Pair slots the layout evaluates (the sum over
length classes of ``nq_c x L_c^2``, ``objective.rank_pair_slots``)
over the ordered document pairs the queries hold (the sum over queries
of ``n_q^2``, ``objective.rank_doc_pairs``: a fact of the data,
whatever the layout). ``None`` where the kind hands no ranking
counters."""


def read(facts):
    rank = facts.get("rank")
    if not rank or not rank.get("doc_pairs"):
        return None
    return rank["pair_slots"] / rank["doc_pairs"]
