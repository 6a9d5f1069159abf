"""Layer: iteration_driver. Seconds of set-up under the program's
``lgbm.setup`` spans (one a booster: the learner and its plan, the
table's hand-over to the device, the objective, the scores), less the
compiles inside."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "booster")
