"""Device time of the traced stretch's ops that no stage of the round step
claims (the scan's loop control, copies the compiler inserts without
metadata, ops of other programs), in ms per round
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    return stages.ms_per_round(ctx, None)
