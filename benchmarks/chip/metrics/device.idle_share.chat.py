"""device.idle_share.chat: the share of the traced window in which no op
ran on the chip, in %.  Device trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
