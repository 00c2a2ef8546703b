"""The whole window over the replans completed in it, in milliseconds:
a replan runs from a frame on the device to host Plans."""


def read(run):
    return 1e3 * run.window.seconds / run.window.frames
