"""Mean seconds per save event in the program span ``ckpt.save.fingerprint``:
slicing each saved unit out of the state, dispatching its fingerprint
program, waiting for the on-device compare with the last committed
fingerprints, fetching the fingerprint tables, packing and hashing them,
and the dedup and delta decisions."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.save.fingerprint")
