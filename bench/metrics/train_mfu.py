"""Model FLOP utilization of the whole window, checkpoint events
included: 6 N_active tokens / window seconds / (chips x bf16 peak), %."""
from bench.common.counts import train_flops
from bench.common.peaks import peaks_for


def read(rec):
    if not rec.get("train_tokens"):
        return None
    peak = peaks_for(rec["device_kind"])["bf16_flops"] * rec["chips"]
    return 100.0 * train_flops(rec["n_active"], rec["train_tokens"]) \
        / rec["window_s"] / peak
