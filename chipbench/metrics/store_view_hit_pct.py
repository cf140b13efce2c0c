"""Read path: share of Get batches the device page pool served through
its resident one-launch store view (``DevicePagePool`` ``store_hits``
over hits and misses in the window), in %."""


def read(ctx):
    hits = ctx.counts["pool.store_hits"]
    total = hits + ctx.counts["pool.store_misses"]
    return None if total == 0 else 100.0 * hits / total
