"""``quality_loss``, as the player model scores the window
(``player.end_to_end``): the mean over chunks ready in the window of the
top fidelity's VBench prior less the chunk's.  In a cell served above
its capacity the share of first chunks (served at the top) in the window
moves it with the order of the arrivals, so it is read here and holds no
bound."""


def read(ctx):
    return ctx.e2e.get("quality_loss")
