"""How unevenly the shards scored the ranked engine's rows over the
window: the most rows one shard scored over the mean of all shards
(``stats["scored_rows_shard<s>"]``; 1.0 is even).  The busiest chip sets
the pace of a row-scoring dispatch.  Read where the engine counts rows per
shard."""


def read(run):
    per = [v for k, v in run.stats.items()
           if k.startswith("scored_rows_shard")]
    mean = sum(per) / len(per) if per else 0
    return max(per) / mean if mean > 0 else None
