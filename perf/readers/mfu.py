"""Forward and backward FLOPs a sample x samples/s of the traced window over chips x peak bf16."""


def read(facts):
    from perf import counts
    tr = facts['trace']
    if not tr or not tr.get('steps'):
        return None
    peaks = counts.load_peaks(facts['device']['kind'])
    rate = tr['steps'] * int(facts['traffic']['batch']) / tr['window_s']
    return 100.0 * counts.train_flops_per_sample(facts['config']) * rate / (facts['chips'] * peaks['bf16_flops_per_s'])
