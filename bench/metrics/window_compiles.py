"""Programs compiled or fetched from the persistent compile cache inside
the window, per round, from JAX's monitoring events: a program re-traced
every round shows here even when the cache serves it."""


def read(ctx):
    return ctx["compiles"] / ctx["rounds"]
