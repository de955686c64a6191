"""Percent of its roofline that ``remove_below`` reaches in the traced
window. SOCCER calls it once per round on every chip, on that chip's
machines' points and the round's centers (the ``round_centers`` its
check keeps); nothing where a fit removes nothing."""
import roofline


def read(run):
    cfg = run.config
    chips = run.device["count"]
    m = cfg["machines"] // chips
    p = cfg["n"] // cfg["machines"]
    calls = [(m, p, cfg["dim"], len(c))
             for f in run.fits for c in f.own.get("round_centers", [])
             for _ in range(chips)]
    return roofline.share(run, "remove_below", calls)
