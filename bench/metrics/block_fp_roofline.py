"""Share of its HBM roofline the fingerprint program reached in the
traced unit, %.  The program is ``block_fp`` with its jitted wrapper
(``jit__fingerprint_many``, one run per saved unit and kind): the bytes
it must move at least (every leaf read once, the per-block tables
written; from the saved leaves' shapes) at the chip's HBM bandwidth,
over the summed device time of its runs.  It does no matrix work, so
bytes bound it.  Nothing is read when the trace's run count differs from
the count the saved units imply."""
from bench.common.peaks import peaks_for
from bench.common.trace import program_seconds

PROGRAM = "jit__fingerprint_many"


def read(rec):
    tr, fp = rec.get("trace"), rec.get("fp_traced")
    if not tr or not fp:
        return None
    seconds, runs = program_seconds(tr, PROGRAM)
    if runs != fp["runs"] or seconds <= 0:
        return None
    bw = peaks_for(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * fp["bytes"] / bw / seconds
