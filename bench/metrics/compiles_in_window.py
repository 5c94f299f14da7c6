"""``compiles_in_window``: backend compiles JAX reported inside the
measured window (its monitoring events); every shape is warmed in set-up,
so a sound run reads 0."""


def read(ctx):
    return float(ctx.window["compiles"])
