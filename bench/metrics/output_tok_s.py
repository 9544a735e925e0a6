"""Output tokens committed in the window, over the window's seconds."""


def read(ctx):
    return ctx.window_tokens() / ctx.seconds
