"""A kernel's share of its roofline, from the trace and a roofline file.

``rooflines/<entry>.py`` holds the regular expression that finds the
entry point's device events and the function that counts its logical
flops and bytes. The least time the chip could take for the window's
calls is the larger of flops over peak FLOP/s and bytes over peak
bandwidth (``peaks.json``, by ``device_kind``); the share is that time
over the events' device time. Summed over every chip and every call, so a
kernel split into more calls counts the same work.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Iterable, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load(entry: str):
    path = os.path.join(HERE, "rooflines", entry + ".py")
    spec = importlib.util.spec_from_file_location(f"roofline_{entry}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def share(run, entry: str, calls: Iterable[Tuple]) -> Optional[float]:
    """Percent of the roofline that ``entry``'s events reach in the traced
    window, for the logical ``calls`` (argument tuples of its ``work``, one
    per call on one chip); None where there is no call or the trace holds
    no such event."""
    calls = list(calls)
    if run.trace is None or not calls:
        return None
    if run.peaks is None:
        raise KeyError(f"no peaks for device kind {run.device['kind']!r} "
                       f"in peaks.json")
    mod = load(entry)
    busy = run.trace.seconds(mod.EVENT)
    if busy <= 0.0:
        return None
    least = 0.0
    for args in calls:
        flops, nbytes = mod.work(*args)
        least += max(flops / run.peaks["flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
