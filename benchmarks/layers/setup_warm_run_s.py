"""Layer: iteration_driver. Seconds of set-up under the program's
``train`` spans before the window (dispatch, the wait for the device,
the trees' bookkeeping), less the compiles inside: the warm-up trees
actually running."""

from .. import setup_spans


def read(facts):
    return setup_spans.seconds(facts, "warm_run")
