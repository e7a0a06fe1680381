"""Tile pairs the attention kernels visited over tile pairs that hold a live pair, all layers, of the window's device counter."""


def read(facts):
    tiles = facts['counters'].get('attention_tiles')
    if not tiles:
        return None
    visited, live = (sum(kind[i] for kind in tiles) for i in (0, 1))
    return visited / live if live else None
