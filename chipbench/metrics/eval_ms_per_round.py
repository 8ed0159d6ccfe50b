"""Device time of eval (the ``eval`` scope: the cond-gated loss and
accuracy on the held-out set) over the traced stretch, in ms per round
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    return stages.ms_per_round(ctx, "eval")
