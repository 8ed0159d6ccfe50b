"""Device time of local training (the ``local_train`` scope: key split,
client gather, the vmapped local SGD steps) over the traced stretch, in
ms per round (``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    return stages.ms_per_round(ctx, "local_train")
