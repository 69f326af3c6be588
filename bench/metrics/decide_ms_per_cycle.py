"""Host time of one tuning decision.

Layer: tuner and build lane (``PredictiveTuner.decide`` in
``core/tuner.py``: classification, what-if utilities, knapsack,
drops and creates, the forecaster's update, with their host reads).
Source: the program's ``repro.tuner.decide`` spans in the window
(``bench/program_spans.py``): their mean length, in ms.  A cycle runs
before the next statement call, which waits for it.  Moves
``stmts_per_s``.
"""

from bench import program_spans as ps


def read(ctx):
    spans = ps.in_window(ctx, (ps.DECIDE,))
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
