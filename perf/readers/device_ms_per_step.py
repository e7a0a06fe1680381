"""Device busy time from the first whole step of the traced slice to the end of the last, over those steps."""


def read(facts):
    return (facts['trace'] or {}).get('device_ms_per_step')
