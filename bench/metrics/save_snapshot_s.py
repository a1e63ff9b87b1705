"""Mean snapshot stage of the window's save events (fingerprint, device
to host copy, packing, the bounded write queue), as the program times it
in ``last_save_stats["snapshot_seconds"]``."""
import statistics


def read(rec):
    stats = rec.get("save_stats")
    return statistics.fmean(s["snapshot_seconds"] for s in stats) \
        if stats else None
