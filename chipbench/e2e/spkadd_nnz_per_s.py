"""Input nonzeros added per second, in millions: every input of every call
in the window over the whole window, generation included."""


def read(win) -> float:
    return win.work / win.window_s / 1e6
