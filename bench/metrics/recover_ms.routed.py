"""Failover (``serve/engine.py`` ``restore_shard``): from the return of the
mix's restore call to the delivery of the first batch, of those released
after the failure, prepared with every shard healthy
(``BatchRecord.shards_healthy``, ``t_delivered``), on the engine's clock
(ms). Batches prepared while the shard was down drain first."""


def read(run):
    f = run.failure
    if not f or f["fail_call"] is None or f["restore_call"] is None:
        return None
    down, back = f["fail_call"][1], f["restore_call"][1]
    full = [b.t_delivered for b in run.batches
            if getattr(b, "shards_healthy", None) == run.chips
            and b.t_release > down and b.t_delivered > back]
    return (min(full) - back) * 1e3 if full else None
