"""Device seconds per fit of the collectives (all-gather, all-reduce,
collective-permute, all-to-all, reduce-scatter), averaged over the chips;
nothing where the traced window ran none."""
import xplane


def read(run):
    if run.trace is None:
        return None
    secs = run.trace.seconds(xplane.COLLECTIVE)
    if secs <= 0.0:
        return None
    return secs / run.trace.chips / len(run.fits)
