"""Frames whose cuts reached the host, over the whole window."""


def read(run):
    return run.window.frames / run.window.seconds
