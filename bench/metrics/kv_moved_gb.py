"""KV bytes the pool moved in the window (``KVPool.transfer_bytes_in`` +
``transfer_bytes_out``: spills and restores), in GB."""


def read(ctx):
    return ctx.kv_bytes / 1e9
