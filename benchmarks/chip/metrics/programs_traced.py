"""Programs traced per fit: the ``programs_traced`` counter, one count
each time JAX traces the body of a SOCCER round or finalize program,
averaged over the window's fits."""
import fit_traces


def read(run):
    return fit_traces.per_fit(
        run, lambda line: line["counters"].get("programs_traced"))
