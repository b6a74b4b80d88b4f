"""``chunk_gap_p90_s``, as the player model scores the window (``player.end_to_end``).
In a cell served above its capacity it swings with the backlog, so it is
read here, beside the end-to-end metrics, and holds no bound."""


def read(ctx):
    return ctx.e2e.get("chunk_gap_p90_s")
