"""Device time of the ``accumulate`` scope, where local training adds each
client's weighted params into the streamed Eq. 18 sum, over the traced
stretch, in ms per round (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "accumulate")
