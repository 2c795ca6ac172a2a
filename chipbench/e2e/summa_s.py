"""Time to C: the window over the multiplies completed in it, in seconds."""


def read(win) -> float:
    return win.window_s / win.steps
