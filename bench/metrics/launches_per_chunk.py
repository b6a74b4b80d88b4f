"""Jitted step launches (``BatchedChunkExecutor.dispatch_count``) in the
window over the chunks completed in it."""


def read(ctx):
    return ctx.dispatches / ctx.chunks_done if ctx.chunks_done else None
