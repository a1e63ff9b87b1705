"""Payload bytes per second of the save's device-to-host copy: the
program's ``d2h_bytes`` counter over its ``ckpt.save.d2h`` span, both
means per save event."""
from bench.common.stages import d2h_bytes_per_s


def read(rec):
    return d2h_bytes_per_s(rec)
