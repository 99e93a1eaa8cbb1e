"""Blocking device-to-host fetches per ``intersect_batch`` call over the
window (the boolean engine's ``device_round_trips`` counter over its
``batches``).  Read in the cells whose operation is ``and``."""


def read(run):
    if run.operation != "and" or not run.stats.get("batches"):
        return None
    if "device_round_trips" not in run.stats:
        return None
    return run.stats["device_round_trips"] / run.stats["batches"]
