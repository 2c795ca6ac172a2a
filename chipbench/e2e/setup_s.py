"""Set-up: process start to the end of warm-up, in seconds."""


def read(win) -> float:
    return win.setup_s
