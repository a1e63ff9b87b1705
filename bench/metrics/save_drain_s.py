"""Mean seconds per save event in the program span ``ckpt.save.drain``:
the synchronous save waiting for its writer lanes to finish the event's
writes."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.save.drain")
