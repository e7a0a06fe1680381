"""Least time at the chip's peaks for a step's delta-rule FLOPs and bytes (perf/work) over the delta rule's kernels' busy time a step."""


def read(facts):
    from perf import harness
    share = harness.load_module("readers", "attention_roofline", facts['root']).kernel_roofline
    return share(facts, "kda_kernel_work", "kda_ms_per_step")
