"""device.idle_share.tp4: the share of the traced window in which no op
ran, busy time averaged over the cell's four chips, in %.  Device
trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
