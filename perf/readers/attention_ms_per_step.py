"""Busy time a step of the attention kernels (forward, dq, dk and dv) in the device trace's op_s."""

KERNELS = "block_diffusion_attention"  # the kernels' names start so (ops/flash_attention.py)


def kernel_seconds(facts, prefix):
    """Busy seconds a step of the device ops whose label starts with ``prefix``; None if none ran."""
    tr = facts['trace']
    if not tr or not tr.get('steps') or not tr.get('op_s'):
        return None
    busy = sum(s for label, s in tr['op_s'].items() if label.startswith(prefix))
    return busy / tr['steps'] if busy else None


def read(facts):
    s = kernel_seconds(facts, KERNELS)
    return s * 1e3 if s else None
