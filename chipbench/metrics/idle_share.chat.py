"""1 - (union of device-operation intervals / traced window)."""

from chipbench.readings import idle_share


def read(run):
    return idle_share(run)
