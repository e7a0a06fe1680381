"""1 - union of device-op intervals over the traced window."""


def read(facts):
    tr = facts['trace']
    return 100.0 * tr['idle_share'] if tr else None
