"""Wall seconds per fit: the window from the start of its first fit to
the end of its last, over the fits completed."""


def read(run):
    return run.window_s / len(run.fits)
