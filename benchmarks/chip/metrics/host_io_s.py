"""Host seconds per fit spent moving the input and the results between
host and device: the ``fit.place``, ``soccer.place`` and
``soccer.readback`` spans of ``fit(trace="full")``, averaged over the
window's fits."""
import fit_traces


def read(run):
    return fit_traces.per_fit(run, lambda line: fit_traces.span_s(
        line, "fit.place", "soccer.place", "soccer.readback"))
