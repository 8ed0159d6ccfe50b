"""Device time of the hybrid LM's ``mlp`` scope (training and eval
forwards, recomputes and backward passes alike) over the traced stretch,
in ms per round (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, "mlp")
