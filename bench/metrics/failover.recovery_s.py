"""Seconds from a killed shard's death to its re-admission, the largest
over the window's kills (``run.failover["recovery_s"]``: the restore from
the arena checkpoint, the upload of the restored slice to the shard's chip,
and the wait for the next wave to install it).  Read in the cells whose
traffic kills shards."""


def read(run):
    times = (run.failover or {}).get("recovery_s") or []
    return max(times) if times else None
