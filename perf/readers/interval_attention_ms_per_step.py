"""Busy time a step of the interval attention kernels (forward, dq, dk and dv) in the device trace's op_s."""

KERNELS = "interval_attention"  # the kernels' names start so (ops/flash_attention.py)


def read(facts):
    from perf import harness
    by_label = harness.load_module("readers", "attention_ms_per_step", facts['root']).kernel_seconds
    s = by_label(facts, KERNELS)
    return s * 1e3 if s else None
