"""Device time of aggregation (the ``aggregate`` scope: the Eq. 18 server
update and the count update) over the traced stretch, in ms per round
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    return stages.ms_per_round(ctx, "aggregate")
