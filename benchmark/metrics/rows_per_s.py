"""Input rows of every aggregate completed in the window, over the whole
window (which ends when the last aggregate started in it finishes)."""


def read(ctx):
    w = ctx.window
    return sum(it.work for it in w.items if it.ok) / w.seconds
