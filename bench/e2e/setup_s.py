"""Set-up: process start to the schedule's time 0 (imports, chip start,
weights, pool fit, engine and every step shape run once)."""


def read(ctx):
    return ctx.setup_s
