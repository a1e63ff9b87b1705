"""Mean seconds per save event in the program span ``ckpt.save.pack``:
turning each fetched leaf into bytes, building the packet and queueing
it on a writer lane (a full queue blocks here)."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.save.pack")
