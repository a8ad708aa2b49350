"""Tokens delivered by every tick of the window over the window's length."""


def read(run):
    return run.window.tokens() / run.window.seconds
