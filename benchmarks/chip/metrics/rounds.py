"""Communication rounds per fit (``ClusterResult.rounds``)."""


def read(run):
    return sum(f.rounds for f in run.fits) / len(run.fits)
