"""Requests completed in the window over the window's seconds."""


def read(run):
    w = run.window
    return len(w.done) / w.seconds if w.seconds > 0 else None
