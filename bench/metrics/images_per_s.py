"""Answers delivered inside the window over the window's length."""


def read(ctx):
    return ctx.window["completed_in_window"] / ctx.window["seconds"]
