"""Share of the traced serving window in which no operation ran on
device 0."""


def read(run):
    return 100.0 * run.summary.idle_share(0)
