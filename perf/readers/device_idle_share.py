"""1 - union of device-op intervals over the traced whole steps; None where the slice held fewer than two."""


def read(facts):
    tr = facts['trace']
    return 100.0 * tr['idle_share'] if tr and tr.get('idle_share') is not None else None
