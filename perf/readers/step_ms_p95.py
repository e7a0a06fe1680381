"""95th percentile of the step program's start-to-start times in the device trace."""


def read(facts):
    return (facts['trace'] or {}).get('step_ms_p95')
