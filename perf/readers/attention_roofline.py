"""Least time at the chip's peaks for a step's attention FLOPs and bytes (perf/work) over the kernels' busy time a step."""


def kernel_roofline(facts, work_name, reader_name):
    """100 x the least time for ``perf/work/<model>.py:<work_name>`` over what ``reader_name`` reads."""
    from perf import counts, harness
    ms = harness.load_module("readers", reader_name, facts['root']).read(facts)
    work = getattr(harness.model_module("work", facts['config'], facts['root']), work_name, None)
    if not ms or work is None:
        return None
    peaks = counts.load_peaks(facts['device']['kind'])
    w = work(facts['config'], facts['traffic'])
    floor_s = max(w['flops'] / peaks['bf16_flops_per_s'], w['bytes'] / peaks['hbm_bytes_per_s'])
    return 100.0 * floor_s * 1e3 / ms


def read(facts):
    return kernel_roofline(facts, "attention_kernel_work", "attention_ms_per_step")
