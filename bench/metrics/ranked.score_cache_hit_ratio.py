"""Share of the arena rows the ranked engine looked up in its hot-block
score cache that it found there, %, over the window
(``ranked_score_cache_rows`` hit / (hit + miss))."""


def read(run):
    hit = run.counters.get("score_cache_hit", 0)
    miss = run.counters.get("score_cache_miss", 0)
    return 100.0 * hit / (hit + miss) if hit + miss else None
