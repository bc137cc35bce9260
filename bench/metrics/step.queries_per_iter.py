"""Mean likelihood queries per chain-iteration over the window
(``StepStats.lik_queries``)."""


def read(ctx):
    return ctx.window["queries_per_iter"]
