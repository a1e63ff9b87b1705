"""Mean seconds per save event in the writer-lane span
``ckpt.write.encode``, summed over the writer threads: building the
chunk items or block-delta records and compressing them (codec ``auto``:
zstd, with a crc32 per record)."""
from bench.common.stages import stage_mean


def read(rec):
    return stage_mean(rec, "ckpt.write.encode")
