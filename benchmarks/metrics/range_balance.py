"""Median, over the window's requeries, of the valid rows of the
answer's fullest partition times the partitions over the rows kept
(``shard_rows_max`` x ``shards`` / ``rows`` of the job's
``dryad:decode:decode`` span): how evenly the elected splitters cut the
table.  1.0 is an even cut; at ``shuffle_slack`` (2.0) a bucket
overflows and the job retries.  ``None`` where the span lacks the
stats (the parent of PR 30)."""

import program_spans as PS


def read(trace, spans, counters, cell):
    if trace is None:
        return None

    def balance(job):
        for span in PS.named(job, "dryad:decode:decode"):
            stats = span.stats
            if stats.get("rows") and "shard_rows_max" in stats:
                return stats["shard_rows_max"] * stats["shards"] / stats["rows"]
        return None

    return PS.median_over_jobs(PS.of(cell, __file__), "bench:requery", balance)
