"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips, in percent."""


def read(run):
    share = run.trace.idle_share()
    return None if share != share else 100.0 * share
