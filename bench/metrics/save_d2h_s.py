"""Mean seconds per save event in the program span ``ckpt.save.d2h``:
dispatching the dirty-block gathers and waiting for the payload's
``jax.device_get``."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.save.d2h")
