"""Least time at the chip's peaks for the step's FLOPs and bytes (perf/counts.py) over device_ms_per_step."""


def read(facts):
    from perf import counts
    ms = (facts['trace'] or {}).get('device_ms_per_step')
    if not ms:
        return None
    peaks = counts.load_peaks(facts['device']['kind'])
    floor = counts.step_floor_seconds(facts['config'], int(facts['traffic']['batch']), peaks)
    return 100.0 * floor['seconds'] * 1e3 / ms
