"""Mean host time of one synchronous ``CheckpointManager.save`` call in
the window (benchmark span ``save``): the loop's stall per event."""
import statistics


def read(rec):
    spans = rec["spans"].get("save")
    return statistics.fmean(spans) if spans else None
