"""Mean over the window's fits of the returned centers' cost on the full
data, by the float32 reference, over the generating means' cost."""


def read(run):
    return sum(run.cost_ratios) / len(run.cost_ratios)
