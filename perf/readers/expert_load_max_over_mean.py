"""Fullest held expert's picks over the mean, of the window's picks by layer and held expert; the worst layer."""


def read(facts):
    picks = facts['counters'].get('expert_picks')
    if not picks:
        return None
    ratios = [max(layer) * len(layer) / sum(layer) for layer in picks if sum(layer)]
    return max(ratios) if ratios else None
