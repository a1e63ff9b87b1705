"""Median host time of one training step in the window: the jitted step
dispatched and its loss fetched (benchmark span ``train_step``)."""
import statistics


def read(rec):
    spans = rec["spans"].get("train_step")
    return statistics.median(spans) if spans else None
