"""The benchmark: the yardstick later PRs are measured with.

Everything here is kept apart from the program (``lightgbm_tpu/``): the
traffic generators, the reduction from traces and counters to metrics,
the table of peaks, the byte functions, the plain references and the
comparison that decides ``correct``. ``README.md`` beside this file
says how a cell is run and how one is added.
"""
