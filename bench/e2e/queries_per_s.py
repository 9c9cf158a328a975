"""Queries answered by the window's close over the window's seconds."""


def read(w):
    return w["answered_by_close"] / w["seconds"] if w["seconds"] else None
