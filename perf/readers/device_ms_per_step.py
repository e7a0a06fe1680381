"""Device busy time of the traced window over the steps that ran in it."""


def read(facts):
    return (facts['trace'] or {}).get('device_ms_per_step')
