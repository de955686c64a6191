"""MB (10^6 bytes) per fit that the busiest device receives while SOCCER
places its input: the ``place_max_device_bytes`` counter of
``fit(trace="full")``, averaged over the window's fits; nothing where the
program keeps no such counter."""
import fit_traces


def read(run):
    def mb(line):
        b = line["counters"].get("place_max_device_bytes")
        return None if b is None else b / 1e6
    return fit_traces.per_fit(run, mb)
