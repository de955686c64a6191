"""Wall seconds per SOCCER round: the ``wall_s`` of the round records of
``fit(trace="full")``, each round synced on the device, over all rounds of
the window's fits."""


def read(run):
    walls = [w for f in run.fits for w in f.round_walls]
    return sum(walls) / len(walls) if walls else None
