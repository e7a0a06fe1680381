"""memory_stats()['peak_bytes_in_use'] of the fullest device, read before the reference runs."""


def read(facts):
    return facts['memory_peak_bytes'] / 1e9 if facts['memory_peak_bytes'] else None
