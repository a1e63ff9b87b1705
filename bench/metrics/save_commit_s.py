"""Mean seconds per save event in the program span ``ckpt.save.commit``:
the durability barrier, the manifest commit, reference counts and
garbage collection."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.save.commit")
