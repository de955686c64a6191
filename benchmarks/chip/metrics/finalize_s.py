"""Wall seconds per fit of SOCCER's finalize program, synced on the
device: the ``soccer.finalize`` span of ``fit(trace="full")``, averaged
over the window's fits."""
import fit_traces


def read(run):
    return fit_traces.per_fit(
        run, lambda line: fit_traces.span_s(line, "soccer.finalize"))
