"""MB (10^6 bytes) per fit that the program moves between host and
device: its ``h2d_bytes`` plus ``d2h_bytes`` counters, the ``nbytes`` of
every host array it puts on a device and every device array it reads
back, averaged over the window's fits."""
import fit_traces


def read(run):
    def mb(line):
        c = line["counters"]
        if "h2d_bytes" not in c and "d2h_bytes" not in c:
            return None
        return (c.get("h2d_bytes", 0) + c.get("d2h_bytes", 0)) / 1e6
    return fit_traces.per_fit(run, mb)
