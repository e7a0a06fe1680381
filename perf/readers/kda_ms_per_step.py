"""Busy time a step of the delta rule's kernels (a chunk's operands and the scan, forward and backward) in the device trace's op_s."""

KERNELS = "kda_"  # the kernels' names start so (ops/delta_rule.py)


def read(facts):
    from perf import harness
    by_label = harness.load_module("readers", "attention_ms_per_step", facts['root']).kernel_seconds
    s = by_label(facts, KERNELS)
    return s * 1e3 if s else None
