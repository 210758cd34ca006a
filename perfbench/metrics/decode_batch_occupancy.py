"""Sequences each decode step advanced, averaged over the window's
decode steps (the engine's decoded tokens over its decode steps, read
around each step). Moves ``serve_tokens_per_s``."""


def read(ctx):
    return ctx.get("occupancy")
