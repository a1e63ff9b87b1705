"""Mean seconds per save event in the writer-lane span
``ckpt.write.store``, summed over the writer threads: framing the object
envelope (``msgpack``) and putting it to the store backend."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.write.store")
