"""Device time of the sampler (the ``sampler`` scope: the Eq. 16 solve and
the pick of the M slots) over the traced stretch, in ms per round
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    return stages.ms_per_round(ctx, "sampler")
