"""Share of the window spent after the client's last call, waiting for the
store's background workers to flush and compact what the window's writes
queued (host clock).  Layer: compaction, the scheduler's workers: the part
of the write rate that the background, not the client, sets."""


def read(ctx):
    return 100.0 * ctx.drain_s / ctx.window_s
