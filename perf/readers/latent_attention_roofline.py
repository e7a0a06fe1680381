"""Least time at the chip's peaks for a step's latent attention FLOPs and bytes (perf/work) over the interval kernels' busy time a step."""


def read(facts):
    from perf import harness
    share = harness.load_module("readers", "attention_roofline", facts['root']).kernel_roofline
    return share(facts, "latent_attention_kernel_work", "interval_attention_ms_per_step")
